"""The deepseek-v3 family (Moonlight) in the benchmark: the plain f32
reference against the program at the SMOKE size on the CPU, through the
benchmark's weights and their mapping onto the program's parameters;
the latent kernel's cost function; the check on a tiny cell, sound and
with a planted fault; and the three readers of the Moonlight cell."""

import gzip
import importlib.util
import json
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import latent_costs, trace
from bench.families import deepseek_v3 as fam
from bench.harness import run_cell, seed_key
from bench.reference import deepseek_v3 as ref
from bench.tests import tiny_mla

SMOKE = {"name": "smoke", "model_type": "deepseek_v3", "hidden_size": 64,
         "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "first_k_dense_replace": 1, "num_hidden_layers": 3,
         "moe_intermediate_size": 32, "n_shared_experts": 2,
         "num_experts_per_tok": 3, "n_routed_experts": 8,
         "router_experts": 8, "held_experts": {"first": 0, "count": 8},
         "scoring_func": "sigmoid", "routed_scaling_factor": 2.446,
         "vocab_size": 256, "rope_theta": 50000.0, "rms_norm_eps": 1e-5,
         "tie_word_embeddings": False, "torch_dtype": "float32"}
#: The program in f32 against the f32 reference: the same mathematics in
#: another order (absorbed against expanded attention, the experts'
#: gates applied before against after the down projection), so they
#: differ by f32 rounding, about 1e-6 of the logits' size. 1e-4 of it
#: leaves room and still fails a dropped expert or a wrong rope (1e-2
#: and more).
F32_TOL = 1e-4


def _share(first, count):
    return dict(SMOKE, n_routed_experts=count,
                held_experts={"first": first, "count": count})


def _program(cfg):
    from repro.models.model import build_model
    return build_model(fam.program_config(cfg))


def _weights(cfg, seed):
    return fam.init_weights(cfg, seed_key(seed))


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 256, n),
                       jnp.int32)


@pytest.mark.parametrize("first,count", [(0, 8), (2, 4)])
def test_reference_matches_program_forward(first, count):
    cfg = _share(first, count)
    w = _weights(cfg, 5)
    tokens = _tokens(48)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(_program(cfg).forward(
            fam.program_params(w), {"tokens": tokens[None]})[0][0])
    got = np.asarray(ref.logits(w, cfg, tokens))
    assert np.abs(got - want).max() <= F32_TOL * np.abs(want).max()


def test_prefill_then_paged_decode_matches_the_reference():
    """What the engine runs (chunked prefill into the latent slab, then
    paged decode) against the reference's full forward, on logits at
    every decoded position."""
    cfg = _share(0, 8)
    w = _weights(cfg, 6)
    m, params = _program(cfg), fam.program_params(w)
    tokens = _tokens(40, seed=1)
    want = np.asarray(ref.logits(w, cfg, tokens))
    page, M, P = 16, 3, 24
    pages = m.init_paged_cache(P, page)
    table = jnp.asarray([5, 11, 2], jnp.int32)
    start, C = 0, 8
    while start < 30:
        n = min(C, 30 - start)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n] = np.asarray(tokens[start:start + n])
        lg, pages = m.prefill_paged_chunk(params, pages, jnp.asarray(chunk),
                                          table, jnp.int32(start),
                                          jnp.int32(n))
        start += n
    scale = np.abs(want).max()
    assert np.abs(np.asarray(lg[0]) - want[29]).max() <= F32_TOL * scale
    for t in range(30, 40):
        lg, pages, _ = m.decode_paged(params, pages, tokens[t:t + 1],
                                   table[None], jnp.asarray([t], jnp.int32),
                                   jnp.ones((1,), bool))
        assert np.abs(np.asarray(lg[0]) - want[t]).max() <= F32_TOL * scale


def test_engine_serves_the_reference_argmax():
    """ContinuousEngine in f32 with three requests in flight: every
    served token is the reference's first choice (gap 0 up to f32
    rounding)."""
    from repro.serve import ContinuousEngine

    cfg = _share(2, 4)
    w = _weights(cfg, 7)
    eng = ContinuousEngine(_program(cfg), fam.program_params(w),
                           max_slots=3, page_size=16, max_len=64,
                           prefill_chunk=8, eos_id=None)
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, 256, n)) for n in (5, 19, 30)]
    rids = [eng.submit(p, 10) for p in prompts]
    eng.run_until_idle()
    for p, rid in zip(prompts, rids):
        out = eng.results[rid]["tokens"]
        seq = jnp.asarray(p + out[:-1], jnp.int32)
        pos = np.arange(len(p) - 1, len(p) - 1 + len(out))
        targets = np.zeros((1, len(seq)), np.int32)
        targets[0, pos] = out
        g = np.asarray(ref.gaps(w, cfg, seq, jnp.asarray(targets)))
        assert g[0, pos].max() <= 1e-4


def test_reference_shares_add_up_to_the_whole_layer():
    """The reference's expert shares (4 chips of 2) add up, the shared
    experts counted once, to its uncut MoE layer."""
    w = _weights(SMOKE, 8)
    c = dict(ref.consts(SMOKE))
    lw = {k: w[k][0].astype(jnp.float32) for k in ref.MOE_KEYS}
    x = jax.random.normal(jax.random.PRNGKey(0), (9, 64))
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref._moe(x, lw, c, False))
        shared = np.asarray(ref._swiglu(False, x, lw["s_gate"], lw["s_up"],
                                        lw["s_down"]))
        parts = []
        for chip in range(4):
            sl = slice(2 * chip, 2 * chip + 2)
            part = dict(lw, **{k: lw[k][sl]
                               for k in ("e_gate", "e_up", "e_down")})
            parts.append(np.asarray(ref._moe(
                x, part, dict(c, first_held=2 * chip), False)))
    np.testing.assert_allclose(sum(parts) - 3 * shared, whole, rtol=1e-5,
                               atol=1e-6 * np.abs(whole).max())


def test_fp8_control_departs_from_the_reference():
    cfg = dict(SMOKE, torch_dtype="bfloat16")
    w = _weights(cfg, 9)
    tokens = _tokens(64, seed=3)
    c = np.asarray(ref.control_argmax(w, cfg, tokens))
    g = np.asarray(ref.gaps(w, cfg, tokens, jnp.asarray(c[None])))[0]
    assert (g > 0).sum() >= 4
    assert g.max() > 0.06


def test_latent_cost_at_moonlight_widths():
    """Per position and layer: 1,152 bytes of latent (576 bf16 lanes)
    and 2 * 16 * (576 + 512) FLOPs; per slot the queries in and the
    latent outputs out."""
    d = dict(H=16, C=576, R=512)
    f, b = latent_costs.latent_decode_cost(d, [3000, 1, 0], rows=3)
    assert f == 2 * 16 * (576 + 512) * 3001
    assert b == 1152 * 3001 + 2 * 3 * 16 * (576 + 512)


def test_tiny_cell_is_correct():
    res = _tiny_run(None)
    assert res["correct"], res["checks"]
    # the cell's counter reader finds the engine's counter
    assert res["metrics"]["held_expert_tokens_per_decode_step"]["value"] > 0


def dropped_expert(engine):
    """A planted fault: the decode step leaves out one held expert's
    part (its weights zeroed), as a share that lost an expert would."""
    fn = engine._decode

    def broken(params, *a):
        moe = params["layers"]["moe"]
        cut = {k: moe[k].at[:, 0].set(0) for k in ("wi", "wg", "wo")}
        params = dict(params, layers=dict(params["layers"],
                                          moe=dict(moe, **cut)))
        return fn(params, *a)
    broken._cache_size = fn._cache_size
    engine._decode = broken


def test_dropped_expert_is_not_correct():
    res = _tiny_run(dropped_expert)
    assert not res["correct"], res["checks"]
    c = res["checks"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def _tiny_run(hook):
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        root = tiny_mla.make_root(tmp)
        return run_cell(root, tiny_mla.CELL, 2**32 + 5, 2.0, True,
                        time.time(), require_tpu=False, engine_hook=hook,
                        compile_cache=False)


# ------------------------------------ the readers on a recorded excerpt

EXCERPT = Path(__file__).resolve().parent / "data" / "doc-chat-excerpt.json.gz"
#: moonlight-16b-a3b as its cell runs it (bench/configs), the dims that
#: ``fam.dims`` gives
DIMS = dict(D=2048, H=16, R=512, rope=64, nope=128, vd=128, C=576,
            F=11264, Fm=1408, L=27, Ld=1, Lm=26, V=163840, E_router=64,
            E_held=8, first_held=0, k=6, Fs=2816)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def chip_trace():
    with gzip.open(EXCERPT, "rt") as f:
        return trace.from_excerpt(json.load(f))


def _metric(name):
    path = Path(__file__).resolve().parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dims_of_the_cell():
    import json as _json
    cfg = _json.loads((Path(__file__).resolve().parents[1] / "configs"
                       / "moonlight-16b-a3b.json").read_text())
    assert fam.dims(cfg) == DIMS


def test_latent_kernel_rule_finds_one_call_per_layer(chip_trace):
    rule = _metric("latent_decode_roofline").is_kernel
    for step in trace.module_spans(chip_trace, "jit_decode_step"):
        calls = [n for n, _, _ in trace.ops_in(chip_trace, [step])
                 if rule(n)]
        assert len(calls) == DIMS["L"]
    prefill = trace.module_spans(chip_trace, "jit_prefill_step")
    assert not [n for n, _, _ in trace.ops_in(chip_trace, prefill)
                if rule(n)]


def test_expert_rule_finds_the_expert_matmuls(chip_trace):
    """Each MoE layer's held experts are read by their gate, up and down
    matmuls; the rule finds ops in every decode step and none that
    reads only the shared experts' or attention's weights."""
    rule = _metric("expert_ms_per_decode_step").is_expert_op
    for step in trace.module_spans(chip_trace, "jit_decode_step"):
        names = [n for n, _, _ in trace.ops_in(chip_trace, [step])
                 if rule(n, DIMS)]
        assert len(names) >= DIMS["Lm"]
        assert not any("[26,2048,2816]" in n and "[26,8," not in n
                       for n in names)


#: the attended positions of the excerpt's two decode steps, from the
#: run's decode-call log (4 live slots)
CALLS = [[0, 0, 0, 925, 0, 6496, 0, 0, 0, 1636, 0, 1897] + [0] * 12,
         [0, 0, 0, 926, 0, 6497, 0, 0, 0, 1637, 0, 1898] + [0] * 12]


def test_readers_on_the_recorded_excerpt(chip_trace):
    steps = trace.module_spans(chip_trace, "jit_decode_step")
    run = SimpleNamespace(
        trace=chip_trace, decode_calls=[np.array(c) for c in CALLS],
        dims=DIMS, peaks=PEAKS,
        counters={"decode_steps": 4, "held_expert_tokens": 390})
    roof = _metric("latent_decode_roofline").read(run)
    assert 10 < roof < 100
    step_ms = trace.median([(e - s) / 1e6 for _, s, e in steps])
    expert = _metric("expert_ms_per_decode_step").read(run)
    # 3.6 GB of held experts a step: at least 4.4 ms at 819 GB/s
    assert 4.4 < expert < step_ms
    held = _metric("held_expert_tokens_per_decode_step").read(run)
    assert held == 97.5


def test_roofline_takes_the_steps_the_trace_kept(chip_trace):
    """The profiler drops the device events after its buffer fills, so
    the trace may hold fewer decode steps than the log: the least time
    is taken over the first logged calls, as many as the trace's steps,
    and calls logged after them change nothing."""
    calls = [np.array(c) for c in CALLS]
    read = _metric("latent_decode_roofline").read
    kept = read(SimpleNamespace(trace=chip_trace, decode_calls=calls,
                                dims=DIMS, peaks=PEAKS))
    later = [np.full(24, 7168)] * 5
    more = read(SimpleNamespace(trace=chip_trace,
                                decode_calls=calls + later, dims=DIMS,
                                peaks=PEAKS))
    assert more == pytest.approx(kept)
    first = read(SimpleNamespace(trace=chip_trace,
                                 decode_calls=later + calls, dims=DIMS,
                                 peaks=PEAKS))
    assert first > 10 * kept


def test_readers_find_nothing_in_a_dense_cell():
    """The qwen cell has neither the kernel, the experts nor the
    counter: each reader reads nothing, and raises nothing."""
    qwen = SimpleNamespace(
        trace={"modules": [("jit_decode_step", 0, 10)],
               "ops": [("%fusion.1 = bf16[16,2816] fusion(...)", 1, 2)],
               "spans": [], "op_stats": {}},
        decode_calls=[np.array([5, 0])],
        dims=dict(D=1024, H=16, K=16, hd=64, F=2816, L=24, V=151936),
        peaks=PEAKS, counters={"decode_steps": 7})
    for name in ("latent_decode_roofline", "expert_ms_per_decode_step",
                 "held_expert_tokens_per_decode_step"):
        assert _metric(name).read(qwen) is None
