"""Per-kernel validation: shape/dtype sweeps, interpret-mode Pallas vs the
pure-jnp oracles in kernels/ref.py, and gradient checks for the
custom-vjp flash attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.decode_attention import (flash_decode, flash_decode_paged,
                                            latent_decode_paged,
                                            paged_block_rows)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_scan import mamba2_chunked
from repro.kernels.rwkv6_scan import rwkv6_chunked

KEY = jax.random.PRNGKey(0)

ATTN_SWEEP = [
    # B, S, H, K, D, causal, dtype
    (2, 256, 8, 4, 64, True, jnp.float32),
    (1, 128, 4, 4, 32, False, jnp.float32),
    (2, 512, 8, 2, 128, True, jnp.float32),
    (1, 256, 4, 2, 112, True, jnp.float32),   # kimi head dim (pad to 128)
    (2, 256, 8, 4, 64, True, jnp.bfloat16),
    (1, 64, 2, 1, 64, True, jnp.float32),     # MHA==GQA(1)
    (1, 600, 4, 2, 64, True, jnp.float32),    # S not a multiple of the block
    (1, 600, 4, 2, 64, False, jnp.float32),   # ... and padded keys masked
]


def _qkv(B, S, H, K, D, dtype):
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (B, S, H, D), dtype),
            jax.random.normal(ks[1], (B, S, K, D), dtype),
            jax.random.normal(ks[2], (B, S, K, D), dtype))


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,K,D,causal,dtype", ATTN_SWEEP)
    def test_forward_matches_oracle(self, B, S, H, K, D, causal, dtype):
        q, k, v = _qkv(B, S, H, K, D, dtype)
        o_ref = ref.attention(q, k, v, causal=causal)
        o = flash_attention(q, k, v, causal=causal, block_q=128,
                            block_k=128, interpret=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.array(o, np.float32),
                                   np.array(o_ref, np.float32),
                                   atol=tol, rtol=tol)

    def test_blocked_ref_matches_oracle(self):
        for (B, S, H, K, D, causal, dtype) in ATTN_SWEEP[:3]:
            q, k, v = _qkv(B, S, H, K, D, dtype)
            o1 = ref.attention(q, k, v, causal=causal)
            o2 = ref.attention_blocked(q, k, v, causal=causal,
                                       block_q=64, block_k=64)
            np.testing.assert_allclose(np.array(o1, np.float32),
                                       np.array(o2, np.float32),
                                       atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("S,D,causal", [
        (128, 64, True),
        # S=200 with 64-blocks: the padded rows and key columns add
        # nothing to any gradient
        (200, 32, True),
        (200, 32, False),
    ])
    def test_gradients_match_oracle(self, S, D, causal):
        B, H, K = 1, 4, 2
        q, k, v = _qkv(B, S, H, K, D, jnp.float32)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, causal=causal, block_q=64,
                                    block_k=64, interpret=True) ** 2).sum()

        def loss_ref(q, k, v):
            return (ref.attention(q, k, v, causal=causal) ** 2).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.array(a), np.array(b),
                                       atol=5e-4, rtol=5e-4)

    def test_noncausal_gradients(self):
        B, S, H, K, D = 1, 128, 2, 2, 32
        q, k, v = _qkv(B, S, H, K, D, jnp.float32)
        g1 = jax.grad(lambda q: (flash_attention(
            q, k, v, causal=False, block_q=64, block_k=64,
            interpret=True) ** 2).sum())(q)
        g2 = jax.grad(lambda q: (ref.attention(
            q, k, v, causal=False) ** 2).sum())(q)
        np.testing.assert_allclose(np.array(g1), np.array(g2),
                                   atol=5e-4, rtol=5e-4)

    @settings(max_examples=8, deadline=None,
              suppress_health_check=[HealthCheck.function_scoped_fixture])
    @given(B=st.integers(1, 2), nheads=st.sampled_from([(4, 4), (8, 2)]),
           S=st.sampled_from([64, 128, 192]),
           D=st.sampled_from([32, 64]))
    def test_property_shapes(self, B, nheads, S, D):
        H, K = nheads
        q, k, v = _qkv(B, S, H, K, D, jnp.float32)
        o_ref = ref.attention(q, k, v, causal=True)
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)
        np.testing.assert_allclose(np.array(o), np.array(o_ref),
                                   atol=2e-5, rtol=2e-5)


class TestFlashDecode:
    @pytest.mark.parametrize("B,S,H,K,D", [
        (2, 256, 8, 4, 64), (3, 300, 4, 2, 128), (1, 128, 4, 4, 32),
        (2, 96, 8, 8, 64),
    ])
    def test_matches_oracle(self, B, S, H, K, D):
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
        lens = jax.random.randint(ks[3], (B,), 1, S + 1)
        o_ref = ref.decode_attention(q, kc, vc, lens)
        o = flash_decode(q, kc, vc, lens, block_k=64, interpret=True)
        np.testing.assert_allclose(np.array(o), np.array(o_ref),
                                   atol=2e-5, rtol=2e-5)

    def test_decode_equals_last_position_of_full(self):
        B, S, H, K, D = 2, 64, 8, 4, 32
        q, k, v = _qkv(B, S, H, K, D, jnp.float32)
        full = ref.attention(q, k, v, causal=True)
        dec = flash_decode(q[:, -1], k, v, jnp.full((B,), S, jnp.int32),
                           block_k=32, interpret=True)
        np.testing.assert_allclose(np.array(full[:, -1]), np.array(dec),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("lens", [
        [0, 0, 0],          # empty rows: defined as zero output
        [128, 128, 128],    # length == padded cache size
        [0, 37, 128],       # mixed, incl. non-block-aligned interior
        [1, 63, 65],        # straddling block_k=64 boundaries
    ])
    def test_ragged_lengths_match_oracle(self, lens):
        """Pallas and the jnp oracle agree on every ragged shape —
        including lengths of 0, where both are defined to emit zeros."""
        B, S, H, K, D = 3, 128, 8, 4, 32
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
        lengths = jnp.asarray(lens, jnp.int32)
        o_ref = ref.decode_attention(q, kc, vc, lengths)
        o = flash_decode(q, kc, vc, lengths, block_k=64, interpret=True)
        np.testing.assert_allclose(np.array(o), np.array(o_ref),
                                   atol=2e-5, rtol=2e-5)
        # zero-length rows must be exactly zero, not a uniform V average
        for b, ln in enumerate(lens):
            if ln == 0:
                assert not np.any(np.array(o[b]))


class TestPagedDecode:
    """Paged flash-decode vs the contiguous oracle: scatter a contiguous
    cache into one layer of a randomly-permuted page slab ``[L, P, page,
    K * D]`` (the other layers hold noise) and the outputs must match
    bit-for-tolerance (page indirection is pure data movement)."""

    @staticmethod
    def _paged_from_contiguous(kc, vc, page, n_pages, seed=0, layers=1,
                               layer=0):
        B, S, K, D = kc.shape
        M = S // page
        rng = np.random.default_rng(seed)
        perm = rng.permutation(np.arange(1, n_pages))[:B * M]
        table = perm.reshape(B, M).astype(np.int32)
        shape = (layers, n_pages, page, K * D)
        k_pages = rng.standard_normal(shape).astype(np.float32)
        v_pages = rng.standard_normal(shape).astype(np.float32)
        for b in range(B):
            for m in range(M):
                rows = slice(m * page, (m + 1) * page)
                k_pages[layer, table[b, m]] = np.asarray(
                    kc[b, rows]).reshape(page, K * D)
                v_pages[layer, table[b, m]] = np.asarray(
                    vc[b, rows]).reshape(page, K * D)
        return jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table)

    @pytest.mark.parametrize("layer", [0, 1])
    @pytest.mark.parametrize("H,K", [(8, 8), (8, 4), (8, 2)])  # G 1, 2, 4
    @pytest.mark.parametrize("lens", [
        [0, 37, 128], [128, 1, 64], [16, 17, 15],
    ])
    def test_paged_matches_contiguous(self, lens, H, K, layer):
        B, S, D = 3, 128, 32
        page, n_pages = 16, 32
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
        lengths = jnp.asarray(lens, jnp.int32)
        kp, vp, table = self._paged_from_contiguous(
            kc, vc, page, n_pages, layers=2, layer=layer)
        o_ref = ref.decode_attention(q, kc, vc, lengths)
        o_pallas = flash_decode_paged(q, kp, vp, layer, table, lengths,
                                      interpret=True)
        o_jnp = ref.paged_decode_attention(q, kp, vp, layer, table, lengths)
        np.testing.assert_allclose(np.array(o_pallas), np.array(o_ref),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.array(o_jnp), np.array(o_ref),
                                   atol=2e-5, rtol=2e-5)
        # zero-length rows must be exactly zero, not a uniform V average
        for b, ln in enumerate(lens):
            if ln == 0:
                assert not np.any(np.array(o_pallas[b]))

    @pytest.mark.parametrize("page", [16, 128])
    def test_paged_at_serving_widths(self, page):
        """qwen1.5-0.5b's attention widths (K=16 kv heads, hd=64, so
        1024 lanes) at the page sizes the engine serves with."""
        B, S, H, K, D = 2, 256, 16, 16, 64
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
        lengths = jnp.asarray([S, page + 3], jnp.int32)
        kp, vp, table = self._paged_from_contiguous(
            kc, vc, page, B * (S // page) + 1, layers=2, layer=1)
        o_ref = ref.decode_attention(q, kc, vc, lengths)
        o = flash_decode_paged(q, kp, vp, 1, table, lengths, interpret=True)
        np.testing.assert_allclose(np.array(o), np.array(o_ref),
                                   atol=2e-5, rtol=2e-5)

    @staticmethod
    def _slab(B, H, K, D, page, lens, seed=0):
        """Random q and a two-layer slab read at layer 1, each slot's pages
        scattered over it: ``(q, k_pages, v_pages, table, lengths)``."""
        rng = np.random.default_rng(seed)
        M = -(-max(max(lens), 1) // page) + 1
        P = B * M + 3
        table = rng.permutation(np.arange(1, P))[:B * M].reshape(B, M)
        k_pages, v_pages = (rng.standard_normal((2, P, page, K * D))
                            .astype(np.float32) for _ in range(2))
        q = rng.standard_normal((B, H, D)).astype(np.float32)
        return (q, k_pages, v_pages, table.astype(np.int32),
                np.asarray(lens, np.int32))

    @pytest.mark.parametrize("page,H,K,lens", [
        (16, 4, 4, "edges"),      # G = 1
        (16, 8, 4, "edges"),      # G = 2
        (16, 16, 4, "edges"),     # G = 4
        (128, 8, 4, "edges"),
        (24, 8, 4, "edges"),      # not whole bf16 tiles: a page a block
        (7, 8, 4, "edges"),       # not whole 8-row tiles: the page grid
        (16, 8, 4, "one_live"),
        (128, 16, 4, "one_live"),
    ])
    def test_walk_matches_oracle(self, page, H, K, lens):
        """Lengths one short of, at and one past a block of the walk, and
        a batch with one live slot: against the oracle, zero-length rows
        exactly zero."""
        D = 32
        T = paged_block_rows(page, K * D)
        lens = [T - 1, T, T + 1] if lens == "edges" else [0, 0, T + 1, 0]
        q, kp, vp, table, lengths = (jnp.asarray(x) for x in self._slab(
            len(lens), H, K, D, page, lens))
        got = np.asarray(flash_decode_paged(q, kp, vp, 1, table, lengths,
                                            interpret=True))
        want = np.asarray(ref.paged_decode_attention(q, kp, vp, 1, table,
                                                     lengths))
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for b, n in enumerate(lens):
            if n == 0:
                assert not got[b].any()

    @pytest.mark.parametrize("dead", ["nan", "outside"])
    @pytest.mark.parametrize("kernel,page", [
        ("gqa", 16), ("gqa", 128), ("gqa", 7),
        ("latent", 16), ("latent", 128),
    ], ids=["16", "128", "7", "latent-16", "latent-128"])
    def test_dead_pages_are_never_read(self, kernel, page, dead):
        """Every page but the slots' live ones holds NaN; the table's dead
        entries name such pages (``nan``) or pages past the slab, which
        the interpreter refuses to copy (``outside``). The output stays
        finite and matches the oracle on the clean slab. Both kernels of
        the walk: ``flash_decode_paged`` over K and V, and
        ``latent_decode_paged`` over the K slab read as latent rows."""
        H, K, D = 8, 4, 32
        T = paged_block_rows(page, K * D)
        lens = [0, 3, T + page + 1, 2 * page - 1]
        q, kp, vp, table, lengths = self._slab(len(lens), H, K, D, page,
                                               lens, seed=1)
        n_live = [-(-n // page) for n in lens]
        live = {int(p) for b, n in enumerate(n_live) for p in table[b, :n]}
        dead_pages = np.array([p not in live for p in range(kp.shape[1])])
        kp_nan, vp_nan, tbl = kp.copy(), vp.copy(), table.copy()
        kp_nan[:, dead_pages] = np.nan
        vp_nan[:, dead_pages] = np.nan
        if dead == "outside":
            for b, n in enumerate(n_live):
                tbl[b, n:] = kp.shape[1] + 1000
        if kernel == "gqa":
            got = np.asarray(flash_decode_paged(
                jnp.asarray(q), jnp.asarray(kp_nan), jnp.asarray(vp_nan), 1,
                jnp.asarray(tbl), jnp.asarray(lengths), interpret=True))
            want = np.asarray(ref.paged_decode_attention(
                jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), 1,
                jnp.asarray(table), jnp.asarray(lengths)))
        else:
            # every head's query on all K * D lanes; values the first 96
            def latent(fn, slab, t, **kw):
                return np.asarray(fn(
                    jnp.asarray(np.tile(q, K)), jnp.asarray(slab), 1,
                    jnp.asarray(t), jnp.asarray(lengths),
                    sm_scale=(K * D) ** -0.5, v_dim=96, **kw))
            got = latent(latent_decode_paged, kp_nan, tbl, interpret=True)
            want = latent(ref.latent_decode_attention, kp, table)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("page,kv_heads,hd,rows", [
        (16, 16, 64, 512),        # qwen1.5-0.5b: 32 pages a block
        (128, 16, 64, 512),
        (48, 16, 64, 384),        # whole 128-lane tiles of scores
        (24, 16, 64, 24),         # not whole bf16 tiles
        (1024, 16, 64, 1024),     # past the double buffer's budget
        (16, 20, 128, 128),       # qwen1.5-4b: 2,560 lanes
        (16, 8, 112, 512),        # kimi-k2: 896 lanes
        (16, 1, 640, 512),        # moonlight-16b-a3b: latent rows of 640
    ])
    def test_block_rows_follow_the_shape(self, page, kv_heads, hd, rows):
        T = paged_block_rows(page, kv_heads * hd)
        assert T == rows and T % page == 0

    def test_gather_round_trip(self):
        """ref.paged gather reconstructs the contiguous cache exactly:
        scatter -> gather is the identity on the valid prefix."""
        B, S, K, D = 2, 64, 2, 16
        page = 8
        kc = jax.random.normal(KEY, (B, S, K, D), jnp.float32)
        kp, _, table = self._paged_from_contiguous(kc, kc, page, 24, seed=3,
                                                   layers=2, layer=1)
        gathered = kp[1, table].reshape(B, S, K, D)
        np.testing.assert_array_equal(np.array(gathered), np.array(kc))


class TestRWKV6:
    @pytest.mark.parametrize("B,S,H,D,chunk", [
        (2, 64, 4, 16, 16), (1, 128, 2, 32, 32), (2, 96, 3, 16, 16),
    ])
    def test_matches_oracle(self, B, S, H, D, chunk):
        ks = jax.random.split(KEY, 5)
        r = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, H, D))
        v = jax.random.normal(ks[2], (B, S, H, D))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, D))) * 0.5 + 0.45
        u = jax.random.normal(ks[4], (H, D)) * 0.1
        st_ = jax.random.normal(KEY, (B, H, D, D)) * 0.1
        o_ref, s_ref = ref.rwkv6_scan(r, k, v, w, u, st_)
        o, s = rwkv6_chunked(r, k, v, w, u, st_, chunk=chunk, interpret=True)
        np.testing.assert_allclose(np.array(o), np.array(o_ref),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.array(s), np.array(s_ref),
                                   atol=1e-4, rtol=1e-4)

    def test_state_carrying_splits_sequence(self):
        """scan(S) == scan(S/2) ∘ scan(S/2) with carried state."""
        B, S, H, D = 1, 64, 2, 16
        ks = jax.random.split(KEY, 5)
        r, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
        w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, D)))
        u = jax.random.normal(ks[4], (H, D)) * 0.1
        o_full, s_full = ref.rwkv6_scan(r, k, v, w, u)
        o1, s1 = ref.rwkv6_scan(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u)
        o2, s2 = ref.rwkv6_scan(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:],
                                u, s1)
        np.testing.assert_allclose(np.array(o_full),
                                   np.concatenate([o1, o2], 1),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.array(s_full), np.array(s2),
                                   atol=1e-4, rtol=1e-4)


class TestMamba2:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (2, 64, 4, 16, 16, 16), (1, 128, 2, 32, 16, 32),
        (2, 96, 3, 16, 32, 16),
    ])
    def test_matches_oracle(self, B, S, H, P, N, chunk):
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        a = -jnp.abs(jax.random.normal(ks[2], (H,)))
        b = jax.random.normal(ks[3], (B, S, N))
        c = jax.random.normal(ks[4], (B, S, N))
        st_ = jax.random.normal(KEY, (B, H, P, N)) * 0.1
        y_ref, h_ref = ref.mamba2_scan(x, dt, a, b, c, st_)
        y, h = mamba2_chunked(x, dt, a, b, c, st_, chunk=chunk,
                              interpret=True)
        np.testing.assert_allclose(np.array(y), np.array(y_ref),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.array(h), np.array(h_ref),
                                   atol=1e-4, rtol=1e-4)

    def test_chunked_ref_bptt_matches_plain_scan(self):
        """The remat-chunked ref recurrence must not change gradients."""
        B, S, H, P, N = 1, 128, 2, 8, 8
        ks = jax.random.split(KEY, 5)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        a = -jnp.abs(jax.random.normal(ks[2], (H,)))
        b = jax.random.normal(ks[3], (B, S, N))
        c = jax.random.normal(ks[4], (B, S, N))

        def loss(x):
            y, _ = ref.mamba2_scan(x, dt, a, b, c)
            return (y ** 2).sum()
        g = jax.grad(loss)(x)
        assert bool(jnp.isfinite(g).all())


class TestLatentDecode:
    """``latent_decode_paged`` (interpret mode) against
    ``ref.latent_decode_attention``: 16 heads reading one latent row per
    position (G = 16), rows zero-padded to whole 128-lane tiles as the
    model stores them, pages scattered over a slab of two layers, ragged
    lengths with a length-0 row and lengths across the walk's blocks of
    512 positions (``paged_block_rows``)."""

    H, R, ROPE, LANES, PAGE = 16, 96, 32, 256, 16

    def _inputs(self, B, M, dtype, seed=0):
        rng = np.random.default_rng(seed)
        P = B * M + 3
        C = self.R + self.ROPE
        slab = np.zeros((2, P, self.PAGE, self.LANES), np.float32)
        slab[..., :C] = rng.standard_normal((2, P, self.PAGE, C))
        q = np.zeros((B, self.H, self.LANES), np.float32)
        q[..., :C] = 2.0 * rng.standard_normal((B, self.H, C))
        table = rng.permutation(np.arange(1, P))[:B * M].reshape(B, M)
        return (jnp.asarray(q, dtype), jnp.asarray(slab, dtype),
                jnp.asarray(table, jnp.int32))

    @pytest.mark.parametrize("layer", [0, 1])
    @pytest.mark.parametrize("lens,dtype", [
        ([0, 37, 700], jnp.float32),
        ([512, 1, 513], jnp.float32),
        ([0, 1024, 16], jnp.bfloat16),
    ])
    def test_matches_oracle(self, lens, dtype, layer):
        from repro.kernels.decode_attention import latent_decode_paged
        q, slab, table = self._inputs(3, 1024 // self.PAGE, dtype)
        lengths = jnp.asarray(lens, jnp.int32)
        scale = (self.R + self.ROPE) ** -0.5
        got = latent_decode_paged(q, slab, layer, table, lengths,
                                  sm_scale=scale, v_dim=self.R,
                                  interpret=True)
        want = ref.latent_decode_attention(
            q.astype(jnp.float32), slab.astype(jnp.float32), layer, table,
            lengths, sm_scale=scale, v_dim=self.R)
        got, want = np.asarray(got, np.float32), np.asarray(want)
        assert got.shape == (3, self.H, self.R)
        # f32: summation order only; bf16: the output's rounding (2**-8
        # of each value) on top of an f32 computation of bf16 inputs
        rel = 2e-5 if dtype == jnp.float32 else 2.0 ** -8
        np.testing.assert_allclose(got, want, rtol=rel,
                                   atol=2e-5 * np.abs(want).max())
        for b, n in enumerate(lens):
            if n == 0:
                assert not got[b].any()

    def test_oracle_is_attention_over_the_gathered_rows(self):
        """The oracle itself: one head's output is the softmax-weighted
        latent part of the rows the table names, up to the length."""
        q, slab, table = self._inputs(2, 8, jnp.float32, seed=1)
        lengths = jnp.asarray([5, 100], jnp.int32)
        o = np.asarray(ref.latent_decode_attention(
            q, slab, 1, table, lengths, sm_scale=0.1, v_dim=self.R))
        b, h = 1, 3
        rows = np.asarray(slab)[1, np.asarray(table)[b]].reshape(-1,
                                                                self.LANES)
        rows = rows[:100]
        s = rows @ np.asarray(q)[b, h] * 0.1
        p = np.exp(s - s.max())
        want = (p / p.sum()) @ rows[:, :self.R]
        np.testing.assert_allclose(o[b, h], want, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("page", [0, 8, 24, 1024])
    def test_illegal_page_rejected(self, page):
        from repro.kernels.decode_attention import check_latent_page_size
        with pytest.raises(ValueError, match="latent_decode_paged"):
            check_latent_page_size(page)
