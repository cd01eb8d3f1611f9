"""Moonlight-16B-A3B's block on the serving path, at its SMOKE size on
the CPU: latent attention (MLA) over the latent page slab, the leading
dense layer, and the dropless routed layer that holds a share of the
experts. The comparison with the plain float32 reference is in
``bench/tests/test_bench_deepseek_v3.py``; here the program is held to
itself: a token's output must not depend on how its prompt was chunked
or on what the other slots hold, the shares of the experts must add up
to the whole layer, and decode must compile once under slot churn."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.moonlight_16b_a3b import SMOKE
from repro.configs.phi3_5_moe import SMOKE as PHI_SMOKE
from repro.configs.qwen1_5_0_5b import SMOKE as QWEN_SMOKE
from repro.models import moe as X
from repro.models.model import build_model
from repro.serve import ContinuousEngine
from repro.serve import engine as engine_mod

PAGE, M = 16, 4
#: f32 on the CPU: two paths of the same arithmetic differ only in the
#: order of their sums (about 1e-6 of the logits' size)
TOL = 1e-5


@pytest.fixture(scope="module")
def mla():
    m = build_model(SMOKE)
    return m, m.init(jax.random.PRNGKey(1))


def _prefill(m, params, pages, prompt, row, chunk):
    """Chunked prefill of ``prompt`` into slot ``row``'s pages, as the
    engine does it; returns the last chunk's logits and the slab."""
    table = jnp.asarray(row, jnp.int32)
    start = 0
    while start < len(prompt):
        n = min(chunk, len(prompt) - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = prompt[start:start + n]
        lg, pages = m.prefill_paged_chunk(params, pages, jnp.asarray(toks),
                                          table, jnp.int32(start),
                                          jnp.int32(n))
        start += n
    return np.asarray(lg[0]), pages


def _tables(B):
    return np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)


@pytest.mark.parametrize("cfg", [SMOKE, PHI_SMOKE], ids=["mla", "gqa"])
def test_output_does_not_depend_on_chunking(cfg):
    """The served routed layer drops nothing: the same prompt prefilled
    in chunks of 3, 8 or 40 gives the same logits, and so does the
    decode step after it. (The capacity layer of training would drop
    tokens of a long chunk that a short one keeps.)"""
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(2))
    prompt = list(np.random.default_rng(0).integers(2, cfg.vocab_size, 37))
    table = _tables(1)
    outs = []
    for chunk in (3, 8, 40):
        pages = m.init_paged_cache(M + 1, PAGE)
        lg, pages = _prefill(m, params, pages, prompt, table[0], chunk)
        nxt = jnp.asarray([int(np.argmax(lg))], jnp.int32)
        dl, _, _ = m.decode_paged(params, pages, nxt, jnp.asarray(table),
                               jnp.asarray([len(prompt)], jnp.int32),
                               jnp.ones((1,), bool))
        outs.append((lg, np.asarray(dl[0])))
    for lg, dl in outs[1:]:
        np.testing.assert_allclose(lg, outs[0][0], atol=TOL, rtol=TOL)
        np.testing.assert_allclose(dl, outs[0][1], atol=TOL, rtol=TOL)


@pytest.mark.parametrize("cfg", [SMOKE, PHI_SMOKE], ids=["mla", "gqa"])
def test_output_does_not_depend_on_other_slots(cfg):
    """A slot's decode logits are the same whether the other slots are
    live with their own prompts or masked off: no capacity is shared."""
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(1)
    B = 4
    table = _tables(B)
    pages = m.init_paged_cache(B * M + 1, PAGE)
    lens, nxt = [], []
    for b in range(B):
        prompt = list(rng.integers(2, cfg.vocab_size, 5 + 9 * b))
        lg, pages = _prefill(m, params, pages, prompt, table[b], 8)
        lens.append(len(prompt))
        nxt.append(int(np.argmax(lg)))
    args = (jnp.asarray(nxt, jnp.int32), jnp.asarray(table),
            jnp.asarray(lens, jnp.int32))
    all_live, _, _ = m.decode_paged(params, pages, *args, jnp.ones((B,), bool))
    alone, _, _ = m.decode_paged(params, pages, *args,
                              jnp.asarray([False, True, False, False]))
    np.testing.assert_allclose(np.asarray(alone[1]),
                               np.asarray(all_live[1]), atol=TOL, rtol=TOL)


def test_expert_shares_add_up_to_the_whole_layer():
    """Four chips of two experts each: every share routes over all 8
    experts and computes its own experts' part plus the shared experts;
    the parts, with the shared experts counted once, are the uncut
    layer."""
    cfg = SMOKE
    p = X.init_moe(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, cfg.d_model))
    whole, n_whole = X.apply_moe_held(p, cfg, x)
    shared = X._expert_ffn(p["shared"]["wi"], p["shared"]["wg"],
                           p["shared"]["wo"], x)
    parts, counts = [], []
    for chip in range(4):
        sl = slice(2 * chip, 2 * chip + 2)
        share = dict(p, **{k: p[k][sl] for k in ("wi", "wg", "wo")})
        y, n = X.apply_moe_held(
            share, cfg.replace(experts_held=2, expert_offset=2 * chip), x)
        parts.append(np.asarray(y))
        counts.append(int(n))
    total = sum(parts) - 3 * np.asarray(shared)
    np.testing.assert_allclose(total, np.asarray(whole), atol=TOL, rtol=TOL)
    assert sum(counts) == int(n_whole) == 2 * 7 * cfg.experts_per_token


def test_choice_bias_picks_but_does_not_weigh():
    """The sigmoid router's bias decides which experts are chosen; the
    gates are the chosen scores without it, normalised, times the
    routed scale."""
    cfg = SMOKE
    p = X.init_moe(jax.random.PRNGKey(6), cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (3, cfg.d_model))
    bias = jnp.zeros((cfg.num_experts,)).at[5].set(10.0)
    gates, idx, scores = X.route(dict(p, bias=bias), cfg, x)
    assert (np.asarray(idx) == 5).any(-1).all()
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    want = chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scale
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)


def test_decode_compiles_once_under_slot_churn(mla):
    """Requests of mixed lengths join and leave the batch: decode and
    prefill each compile once, and every request is answered."""
    m, params = mla
    eng = ContinuousEngine(m, params, max_slots=3, page_size=PAGE,
                           max_len=64, prefill_chunk=8, eos_id=None)
    rng = np.random.default_rng(2)
    rids = []
    for i, (p, n) in enumerate([(5, 9), (20, 3), (3, 12), (33, 6), (9, 2),
                                (14, 7)]):
        rids.append(eng.submit(list(rng.integers(2, 256, p)), n))
        if i % 2:
            eng.step()
    eng.run_until_idle()
    assert all(len(eng.results[r]["tokens"]) > 0 for r in rids)
    assert eng.decode_compiles == 1
    assert eng._prefill_chunk._cache_size() == 1


def test_held_expert_counter(mla):
    """``held_expert_tokens`` counts each live token's chosen experts in
    every MoE layer: all of them here (every expert held), and only the
    held ones when the model holds a share."""
    m, params = mla
    n_moe = SMOKE.num_layers - SMOKE.first_dense_layers
    eng = ContinuousEngine(m, params, max_slots=2, page_size=PAGE,
                           max_len=64, prefill_chunk=8, eos_id=None)
    eng.submit([3, 4, 5], 6)
    eng.submit([7, 8], 4)
    eng.run_until_idle()
    decoded = (6 - 1) + (4 - 1)
    assert eng.metrics["held_expert_tokens"] == \
        decoded * n_moe * SMOKE.experts_per_token

    cfg = SMOKE.replace(experts_held=2, expert_offset=3)
    share = build_model(cfg)
    sp = share.init(jax.random.PRNGKey(1))
    eng = ContinuousEngine(share, sp, max_slots=2, page_size=PAGE,
                           max_len=64, prefill_chunk=8, eos_id=None)
    eng.submit([3, 4, 5], 6)
    eng.run_until_idle()
    got = eng.metrics["held_expert_tokens"]
    assert 0 <= got < 5 * n_moe * SMOKE.experts_per_token


def test_models_without_experts_pay_nothing():
    """A dense model's decode step returns its tokens alone, and its
    engine keeps no expert counter."""
    m = build_model(QWEN_SMOKE)
    params = m.init(jax.random.PRNGKey(0))
    eng = ContinuousEngine(m, params, max_slots=2, page_size=8, max_len=32)
    assert "held_expert_tokens" not in eng.metrics
    pages = m.init_paged_cache(9, 8)
    toks, _ = engine_mod.make_decode_step(m)(
        params, pages, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.ones((2,), bool))
    assert toks.shape == (2,)


def test_decode_span_carries_the_held_expert_count(mla, tmp_path):
    m, params = mla
    eng = ContinuousEngine(m, params, max_slots=2, page_size=PAGE,
                           max_len=64, prefill_chunk=8, eos_id=None)
    eng.submit([3, 4, 5], 4)
    eng.submit([6, 7], 3)
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_idle()
    path = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")[0]
    data = jax.profiler.ProfileData.from_file(path)
    got = [dict(e.stats)["held_expert_tokens"] for plane in data.planes
           if plane.name.startswith("/host:") for line in plane.lines
           for e in line.events if e.name == engine_mod.SPAN_DECODE]
    assert sum(got) == eng.metrics["held_expert_tokens"] > 0


def test_latent_slab_and_page_check():
    m = build_model(SMOKE)
    pages = m.init_paged_cache(9, PAGE)
    assert list(pages) == ["kv_pages"]
    # 32 latent + 8 rope lanes, padded to one 128-lane tile
    assert pages["kv_pages"].shape == (SMOKE.num_layers, 9, PAGE, 128)
    with pytest.raises(ValueError, match="latent_decode_paged"):
        ContinuousEngine(m, m.init(jax.random.PRNGKey(0)), page_size=8)


def test_contiguous_cache_refuses_latent_attention(mla):
    m, params = mla
    with pytest.raises(ValueError, match="latent attention"):
        m.prefill(params, {"tokens": jnp.zeros((1, 4), jnp.int32)}, 16)
    with pytest.raises(ValueError, match="latent attention"):
        m.init_cache(1, 16)


def test_forward_matches_the_paged_path(mla):
    """The full-sequence forward and chunked prefill then paged decode
    run the same block: their logits agree."""
    m, params = mla
    toks = np.random.default_rng(3).integers(2, 256, 21)
    full, _ = m.forward(params, {"tokens": jnp.asarray(toks[None])})
    pages = m.init_paged_cache(M + 1, PAGE)
    table = _tables(1)
    lg, pages = _prefill(m, params, pages, list(toks[:20]), table[0], 8)
    np.testing.assert_allclose(lg, np.asarray(full[0, 19]), atol=TOL,
                               rtol=TOL)
    dl, _, _ = m.decode_paged(params, pages, jnp.asarray(toks[20:21]),
                           jnp.asarray(table), jnp.asarray([20], jnp.int32),
                           jnp.ones((1,), bool))
    np.testing.assert_allclose(np.asarray(dl[0]), np.asarray(full[0, 20]),
                               atol=TOL, rtol=TOL)


def test_published_parameter_counts():
    total, active = get_config("moonlight-16b-a3b").param_counts()
    assert 15.5e9 < total < 16.5e9          # 16B
    assert 2.5e9 < active < 3.3e9           # A3B
