"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each Pallas kernel's test sweeps
shapes/dtypes and asserts allclose against the function here. They are
also the CPU execution path (``ops.py`` dispatches to them off-TPU), so
the multi-pod dry-run lowers these exact computations.

Conventions: inputs arrive in model dtype (bf16/f32); softmax and
accumulations are f32; outputs are cast back to the query dtype.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Attention (prefill / training): causal GQA
# ---------------------------------------------------------------------------


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, sm_scale: Optional[float] = None,
              logits_soft_cap: Optional[float] = None) -> jax.Array:
    """Multi-head attention with grouped KV heads (naive; the oracle).

    q: [B, S, H, D]; k, v: [B, T, K, D] with H % K == 0 (T == S if causal).
    Returns [B, S, H, D] in q.dtype. Materializes the full [S, T] logits —
    use :func:`attention_blocked` for long sequences.
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    assert H % K == 0, (H, K)
    G = H // K
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, S, K, G, D)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    if causal:
        assert S == T, "causal attention requires S == T"
        mask = jnp.tril(jnp.ones((S, T), dtype=bool))
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vf)
    return out.reshape(B, S, H, D).astype(q.dtype)


def attention_blocked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, sm_scale: Optional[float] = None,
                      logits_soft_cap: Optional[float] = None,
                      block_q: int = 512, block_k: int = 512) -> jax.Array:
    """Flash-style blocked attention in pure JAX (online softmax).

    Structure mirrors the Pallas kernel: a static outer loop over query
    blocks, each with a ``lax.scan`` over exactly the kv blocks it needs
    (qi+1 for causal rows), carrying only the small (m, l, acc) online-
    softmax state and emitting each output block once. Memory is
    O(S·block) instead of O(S²), causal FLOPs are exact (no masked waste
    beyond the diagonal block), and the byte pattern matches a fused flash
    implementation — which is what the dry-run roofline should see.
    """
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    scale = sm_scale if sm_scale is not None else D ** -0.5
    # largest block that divides both S and T (prefix lengths vary: 33024
    # for vlm prefill = 32768 tokens + 256 patches)
    for cand in (block_q, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        if cand <= min(S, T) and S % cand == 0 and T % cand == 0:
            block_q = block_k = cand
            break
    nq, nk = S // block_q, T // block_k
    if causal:
        assert S == T and block_q == block_k

    # [B, K, nq, block_q, G, D] query blocks; KV: [B, K, nk, block_k, D]
    # (kept in input dtype; blocks are cast to f32 per-iteration)
    qf = q.reshape(B, S, K, G, D)
    qf = jnp.moveaxis(qf.reshape(B, nq, block_q, K, G, D), 3, 1)
    kf = jnp.moveaxis(k.reshape(B, nk, block_k, K, D), 3, 1)
    vf = jnp.moveaxis(v.reshape(B, nk, block_k, K, D), 3, 1)

    pos_q = jnp.arange(block_q)
    pos_k = jnp.arange(block_k)

    def q_block(qi: int):
        qb = qf[:, :, qi].astype(jnp.float32) * scale      # [B, K, bq, G, D]
        n_kv = (qi + 1) if causal else nk

        def body(carry, ki):
            m, l, acc = carry
            # dynamic-index the shared KV (a [:n_kv] prefix slice per q
            # block would materialize O(nq) partial copies of the cache)
            kb = jax.lax.dynamic_index_in_dim(kf, ki, 2, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vf, ki, 2, keepdims=False)
            kb = kb.astype(jnp.float32)
            vb = vb.astype(jnp.float32)
            s = jnp.einsum("bkqgd,bksd->bkqgs", qb, kb)
            if logits_soft_cap is not None:
                s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
            if causal:
                mask = ((qi * block_q + pos_q)[:, None] >=
                        (ki * block_k + pos_k)[None, :])
                s = jnp.where(jnp.logical_or(ki < qi,
                                             mask[None, None, :, None, :]),
                              s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bkqgs,bksd->bkqgd",
                                                      p, vb)
            return (m_new, l, acc), None

        m0 = jnp.full((B, K, block_q, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, block_q, G), jnp.float32)
        acc0 = jnp.zeros((B, K, block_q, G, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0),
                                      jnp.arange(n_kv))
        return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)

    blocks = [q_block(qi) for qi in range(nq)]             # [B, K, bq, G, D]
    out = jnp.stack(blocks, axis=2)                        # [B, K, nq, bq, G, D]
    out = jnp.moveaxis(out, 1, 3).reshape(B, S, K, G, D)
    return out.reshape(B, S, H, D)


# ---------------------------------------------------------------------------
# Decode attention: one new token against a KV cache
# ---------------------------------------------------------------------------


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *,
                     sm_scale: Optional[float] = None) -> jax.Array:
    """Single-position attention against a (padded) KV cache.

    q: [B, H, D] (the new token's queries)
    k_cache, v_cache: [B, S_max, K, D]
    lengths: [B] int32 — number of valid cache entries per sequence
    Returns [B, H, D]. Rows with ``lengths == 0`` return zeros (nothing
    to attend to), matching the flash-decode kernel, whose online-
    softmax accumulator never runs for a zero-length row — the finite
    NEG_INF mask alone would instead softmax to a uniform average.
    """
    B, H, D = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(B, K, G, D) * scale
    # keep the cache in its storage dtype: the MXU accumulates in f32 via
    # preferred_element_type, and HBM traffic stays at bf16 width
    logits = jnp.einsum("bkgd,bskd->bkgs", qf.astype(k_cache.dtype), k_cache,
                        preferred_element_type=jnp.float32)
    valid = jnp.arange(S)[None, :] < lengths[:, None]          # [B, S]
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    out = out * (lengths > 0).astype(out.dtype)[:, None, None, None]
    return out.reshape(B, H, D).astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, layer, page_table: jax.Array,
                           lengths: jax.Array, *,
                           sm_scale: Optional[float] = None) -> jax.Array:
    """Decode attention of one layer through the paged KV slab (the oracle).

    q: [B, H, D]; k_pages, v_pages: [L, P, page_size, K * D] — the whole
    serving slab, kv heads side by side on the minor dim; layer: the
    layer to read; page_table: [B, M] int32 — per-sequence page ids
    (entries past the allocated prefix point at the reserved null page 0
    and are masked by ``lengths``); lengths: [B] valid tokens. Head
    ``kv`` of token ``t`` of sequence ``b`` lives at ``[layer,
    page_table[b, t // page_size], t % page_size, kv * D:(kv + 1) * D]``.
    Gathers each sequence's pages into the contiguous [B, M * page_size,
    K, D] view and defers to :func:`decode_attention`, so paged and
    contiguous decode are numerically identical by construction.
    """
    B, _, D = q.shape
    page_size, KD = k_pages.shape[2:]
    S = page_table.shape[1] * page_size
    kc = k_pages[layer, page_table].reshape(B, S, KD // D, D)
    vc = v_pages[layer, page_table].reshape(B, S, KD // D, D)
    return decode_attention(q, kc, vc, lengths, sm_scale=sm_scale)


def latent_decode_attention(q: jax.Array, kv_pages: jax.Array, layer,
                            page_table: jax.Array, lengths: jax.Array, *,
                            sm_scale: float, v_dim: int) -> jax.Array:
    """Latent (MLA) decode attention of one layer through the paged latent
    slab (the oracle).

    q: [B, H, C] absorbed queries; kv_pages: [L, P, page_size, C] — the
    whole slab of latent rows; page_table: [B, M]; lengths: [B]. Row
    ``t`` of sequence ``b`` lives at ``[layer, page_table[b, t //
    page_size], t % page_size]`` and is every head's key (all ``C``
    lanes) and value (the first ``v_dim``). f32 softmax of ``q . row *
    sm_scale`` over the first ``lengths[b]`` rows; a length-0 row gives
    zeros. Returns [B, H, v_dim] in q.dtype.
    """
    B, H, C = q.shape
    page_size = kv_pages.shape[2]
    S = page_table.shape[1] * page_size
    rows = kv_pages[layer, page_table].reshape(B, S, C).astype(jnp.float32)
    s = jnp.einsum("bhc,bsc->bhs", q.astype(jnp.float32), rows) * sm_scale
    valid = jnp.arange(S)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bsr->bhr", p, rows[..., :v_dim])
    o = jnp.where((lengths > 0)[:, None, None], o, 0.0)
    return o.astype(q.dtype)


def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, layer, page_table: jax.Array,
                            start, n_valid, *,
                            sm_scale: Optional[float] = None) -> jax.Array:
    """Chunked prefill attention of one layer for ONE request through its
    page-table row (the oracle; also the path off a TPU, and on one for
    pages that are not whole 8-row tiles or heads that do not tile 128
    lanes).

    q: [C, H, D], the chunk's queries at positions ``start ..``; k_pages,
    v_pages: [L, P, page_size, K * D], the whole slab, the chunk's K/V
    already written; page_table: [M], the request's row; start: positions
    cached before the chunk; n_valid: its real rows (unused here: a row
    past it is padding, whose output nobody reads). The request's whole
    window of ``M * page_size`` positions is gathered and attended
    densely under the shifted causal mask ``col <= start + row``, f32
    softmax: ``attention``'s semantics continued from a cache. Returns
    [C, H, D] in q.dtype.
    """
    del n_valid
    C, H, D = q.shape
    page_size = k_pages.shape[2]
    M = page_table.shape[0]
    K = k_pages.shape[3] // D
    G = H // K
    kc = k_pages[layer, page_table].reshape(M * page_size, K, D)
    vc = v_pages[layer, page_table].reshape(M * page_size, K, D)
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qf = q.astype(jnp.float32).reshape(C, K, G, D) * scale
    logits = jnp.einsum("qkgd,skd->kgqs", qf, kc.astype(jnp.float32))
    cols = jnp.arange(M * page_size, dtype=jnp.int32)[None, :]
    causal = cols <= (start + jnp.arange(C, dtype=jnp.int32))[:, None]
    logits = jnp.where(causal[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", probs, vc.astype(jnp.float32))
    return o.reshape(C, H, D).astype(q.dtype)


def latent_prefill_attention(q: jax.Array, kv_pages: jax.Array, layer,
                             page_table: jax.Array, start, n_valid, *,
                             sm_scale: float, v_dim: int) -> jax.Array:
    """Chunked latent (MLA) prefill attention of one layer for ONE request
    (the oracle): q [C, H, C_lanes], the absorbed queries; kv_pages [L, P,
    page_size, C_lanes], the latent slab, the chunk's rows already
    written; the rest as in :func:`paged_prefill_attention`. The window's
    rows are every head's keys (all lanes) and values (the first
    ``v_dim``), attended in f32 under ``col <= start + row``. Returns
    [C, H, v_dim] in q.dtype.
    """
    del n_valid
    C = q.shape[0]
    page_size = kv_pages.shape[2]
    M = page_table.shape[0]
    rows = kv_pages[layer, page_table].reshape(M * page_size, -1)
    rf = rows.astype(jnp.float32)
    causal = (jnp.arange(M * page_size, dtype=jnp.int32)[None, :]
              <= (start + jnp.arange(C, dtype=jnp.int32))[:, None])
    s = jnp.einsum("qhc,tc->hqt", q.astype(jnp.float32), rf) * sm_scale
    s = jnp.where(causal[None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqt,tr->qhr", probs, rf[..., :v_dim])
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Chunked time scan (bounded-memory BPTT for the recurrences)
# ---------------------------------------------------------------------------


def _chunked_time_scan(step, state, xs, chunk: int = 64):
    """Two-level scan: outer over chunks (rematerialized), inner over
    steps. Naive BPTT through a length-S scan saves the carry every step
    (e.g. 4 MB x 4096 steps = 16 GB/device for rwkv6 at train_4k); with
    remat chunking the backward keeps S/chunk checkpoints + one chunk of
    transients — the standard production treatment of linear recurrences.
    """
    S = xs[0].shape[0]
    if S <= chunk or S % chunk != 0:
        return jax.lax.scan(step, state, xs)
    n = S // chunk
    xs_c = tuple(x.reshape(n, chunk, *x.shape[1:]) for x in xs)

    @jax.checkpoint
    def chunk_body(s, xc):
        return jax.lax.scan(step, s, xc)

    state, ys = jax.lax.scan(chunk_body, state, xs_c)
    ys = jax.tree.map(lambda y: y.reshape(S, *y.shape[2:]), ys)
    return state, ys


# ---------------------------------------------------------------------------
# RWKV6 "Finch" WKV scan (data-dependent decay)
# ---------------------------------------------------------------------------


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, state: Optional[jax.Array] = None,
               ) -> Tuple[jax.Array, jax.Array]:
    """RWKV6 recurrence.

    r, k, v: [B, S, H, D]; w: [B, S, H, D] (per-step decay, in (0,1));
    u: [H, D] bonus for the current token. state: [B, H, D, D] or None.

        S_t = diag(w_t) @ S_{t-1} + k_t^T v_t
        o_t = r_t @ (S_{t-1} + diag(u) k_t^T v_t)

    Returns (out [B, S, H, D], final state [B, H, D, D]).
    """
    B, S, H, D = r.shape
    rf, kf, vf = (x.astype(jnp.float32) for x in (r, k, v))
    wf = w.astype(jnp.float32)
    uf = u.astype(jnp.float32)
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)
    else:
        state = state.astype(jnp.float32)

    def step(s, inputs):
        rt, kt, vt, wt = inputs  # [B, H, D]
        kv = kt[..., :, None] * vt[..., None, :]          # [B, H, D, D]
        out = jnp.einsum("bhd,bhde->bhe", rt, s + uf[None, :, :, None] * kv)
        s = wt[..., :, None] * s + kv
        return s, out

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (rf, kf, vf, wf))
    state, outs = _chunked_time_scan(step, state, xs)
    return jnp.moveaxis(outs, 0, 1).astype(r.dtype), state


# ---------------------------------------------------------------------------
# Mamba2 SSD scan (scalar-per-head decay)
# ---------------------------------------------------------------------------


def mamba2_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, state: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Mamba-2 state space duality recurrence.

    x:  [B, S, H, P]   (P = head dim)
    dt: [B, S, H]      (positive step sizes)
    a:  [H]            (negative; decay = exp(a * dt))
    b, c: [B, S, N]    (N = ssm state size; B/C shared across heads)
    state: [B, H, P, N] or None.

        h_t = exp(a dt_t) h_{t-1} + dt_t * x_t b_t^T
        y_t = h_t c_t
    Returns (y [B, S, H, P], final state [B, H, P, N]).
    """
    Bsz, S, H, P = x.shape
    N = b.shape[-1]
    xf, dtf, bf, cf = (t.astype(jnp.float32) for t in (x, dt, b, c))
    af = a.astype(jnp.float32)
    if state is None:
        state = jnp.zeros((Bsz, H, P, N), jnp.float32)
    else:
        state = state.astype(jnp.float32)

    def step(h, inputs):
        xt, dtt, bt, ct = inputs  # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(af[None, :] * dtt)                     # [B, H]
        dbx = (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        h = decay[..., None, None] * h + dbx                   # [B,H,P,N]
        y = jnp.einsum("bhpn,bn->bhp", h, ct)
        return h, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(bf, 1, 0), jnp.moveaxis(cf, 1, 0))
    state, ys = _chunked_time_scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), state
