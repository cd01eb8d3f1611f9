"""Compile the serving path's Pallas kernels and steps for a described
TPU v5e.

Interpret mode (tests/test_kernels.py) checks what the kernels compute,
not whether the TPU compiler accepts their tiling. These tests compile
``flash_decode_paged``, ``flash_decode`` and the ``flash_attention``
forward at qwen1.5-0.5b's serving widths (16 query and 16 kv heads, head
dim 64, bfloat16; 8 slots of 2048 positions) for one chip of a described
``v5e:2x2`` topology, with no chip attached. They also compile
``ContinuousEngine``'s decode and prefill steps for qwen1.5-0.5b at
those sizes and check that the page slab stays whole: no temporaries
and no data move as large as one layer of it. The same holds for
moonlight-16b-a3b's latent kernel ``latent_decode_paged`` and its two
steps, at the benchmark cell's sizes (24 slots of 7,168 positions, 8 of
64 experts held), whose decode step hands the latent kernel the same
scalar operands as ``flash_decode_paged``. Nothing runs, so nothing
about results or speed is checked here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import math
import os
import re
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.moonlight_16b_a3b import CONFIG as MOONLIGHT
from repro.configs.qwen1_5_0_5b import CONFIG as QWEN
from repro.kernels import ops
from repro.kernels.decode_attention import (MAX_BLOCK_ELEMS, check_page_size,
                                            flash_decode, flash_decode_paged,
                                            latent_decode_paged)
from repro.kernels.flash_attention import flash_attention
from repro.models.model import build_model
from repro.serve.engine import make_decode_step, make_prefill_step

SLOTS, H, K, HD, MAX_LEN, PAGE, CHUNK = 8, 16, 16, 64, 2048, 16, 128
#: Seconds one compile may take; each runs about 1-5 s on a CPU core.
COMPILE_S = 180


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lower_and_compile(fn, *args, **jit_kw):
    """``jax.jit(fn).lower(*args).compile()``, failing past COMPILE_S
    (the compile itself cannot be stopped: it is left to finish)."""
    pool = ThreadPoolExecutor(1)
    try:
        job = pool.submit(
            lambda: jax.jit(fn, **jit_kw).lower(*args).compile())
        return job.result(timeout=COMPILE_S)
    finally:
        pool.shutdown(wait=False)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = _lower_and_compile(fn, *args).as_text()
    assert "tpu_custom_call" in text


def _paged_shapes(heads, kv_heads, hd, page, M, layers=2):
    P = SLOTS * M + 1
    slab = ((layers, P, page, kv_heads * hd), jnp.bfloat16)
    return (((SLOTS, heads, hd), jnp.bfloat16), slab, slab,
            ((), jnp.int32), ((SLOTS, M), jnp.int32), ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("page", [16, 128])
def test_flash_decode_paged_compiles(one_chip, page):
    _compile(flash_decode_paged, one_chip,
             *_paged_shapes(H, K, HD, page, MAX_LEN // page))


@pytest.mark.parametrize("heads,kv_heads,hd", [
    (16, 16, 64),    # qwen1.5-0.5b
    (32, 8, 128),    # llama3-8b, phi3.5-moe
    (64, 8, 112),    # kimi-k2
])
def test_flash_decode_paged_compiles_at_page_limit(one_chip, heads, kv_heads,
                                                   hd):
    """The largest page ``check_page_size`` admits fits VMEM."""
    page = MAX_BLOCK_ELEMS // (-(-kv_heads * hd // 128) * 128)
    check_page_size(page, kv_heads, hd)
    with pytest.raises(ValueError, match="page_size"):
        check_page_size(page + 1, kv_heads, hd)
    _compile(flash_decode_paged, one_chip,
             *_paged_shapes(heads, kv_heads, hd, page, 2))


_HLO_OP = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")
_MOVES = ("copy", "copy-start", "transpose", "dynamic-slice",
          "dynamic-update-slice")


def _large_moves(hlo: str, limit: int) -> list:
    """Data moves of the optimized HLO whose output holds ``limit``
    bytes or more: a move by opcode, or a fusion named after one."""
    found = []
    for line in hlo.splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, dtype, dims, opcode = m.groups()
        if not (opcode in _MOVES or (opcode == "fusion"
                                     and any(w in name for w in _MOVES))):
            continue
        bits = 8 if dtype == "pred" else int(
            re.match(r"[a-z]+(\d+)", dtype).group(1))
        n = math.prod(int(d) for d in dims.split(",") if d)
        if n * bits // 8 >= limit:
            found.append(line.strip()[:160])
    return found


def _engine_step(model, one_chip, step, slots, max_len):
    """The engine's ``step`` program for ``model``, compiled with the slab
    donated; returns ``(compiled, pages)`` (shapes)."""
    M = max_len // PAGE

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(model.abstract_params())
    pages = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(slots * M + 1, PAGE)))
    if step == "decode":
        fn = make_decode_step(model)
        args = (params, pages, i32(slots), i32(slots, M), i32(slots),
                jax.ShapeDtypeStruct((slots,), bool, sharding=one_chip))
    else:
        fn = make_prefill_step(model)
        args = (params, pages, i32(1, CHUNK), i32(M), i32(), i32())
    return _lower_and_compile(fn, *args, donate_argnums=(1,)), pages


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_engine_step_keeps_the_slab_whole(one_chip, monkeypatch, step):
    """The engine's steps carry the donated slab through the layer loop
    and write it in place: no temporaries and no copy, transpose or
    slice as large as one layer of it. A slab passed through the scan as
    xs, or one whose minor dim is a head of 64, makes XLA slice or relay
    out each layer's part of it on every step."""
    monkeypatch.setattr(ops, "pallas_mode", lambda: "tpu")
    model = build_model(QWEN)
    compiled, pages = _engine_step(model, one_chip, step, SLOTS, MAX_LEN)
    slab = pages["k_pages"]
    layer = (math.prod(slab.shape) // QWEN.num_layers
             * slab.dtype.itemsize)                  # one layer's keys
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * layer, f"{temp} B of temporaries"
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (step == "decode")
    kernel = re.search(r"^\s*%flash_decode_paged\S* = .* custom-call\(", text,
                       re.M)
    assert (kernel is not None) == (step == "decode")
    assert _large_moves(text, layer) == []


def test_flash_decode_compiles(one_chip):
    _compile(flash_decode, one_chip,
             ((SLOTS, H, HD), jnp.bfloat16),
             ((SLOTS, MAX_LEN, K, HD), jnp.bfloat16),
             ((SLOTS, MAX_LEN, K, HD), jnp.bfloat16),
             ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("seq", [1024, 600])
def test_flash_attention_forward_compiles(one_chip, seq):
    _compile(flash_attention, one_chip,
             ((1, seq, H, HD), jnp.bfloat16),
             ((1, seq, K, HD), jnp.bfloat16),
             ((1, seq, K, HD), jnp.bfloat16))


#: moonlight-16b-a3b as its benchmark cell serves it
ML_SLOTS, ML_MAX_LEN = 24, 7168


def test_latent_decode_paged_compiles(one_chip):
    """Moonlight's widths: 16 heads, latent rows of 576 lanes stored in
    640, 24 slots of 448 pages of 16."""
    M = ML_MAX_LEN // PAGE
    lanes = 640
    _compile(lambda q, s, layer, t, n: latent_decode_paged(
        q, s, layer, t, n, sm_scale=192 ** -0.5, v_dim=512), one_chip,
        ((ML_SLOTS, 16, lanes), jnp.bfloat16),
        ((2, ML_SLOTS * M + 1, PAGE, lanes), jnp.bfloat16),
        ((), jnp.int32), ((ML_SLOTS, M), jnp.int32),
        ((ML_SLOTS,), jnp.int32))


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_engine_step_keeps_the_latent_slab_whole(one_chip, monkeypatch,
                                                 step):
    """Moonlight at its published widths, all 27 layers, 8 experts held,
    at the cell's slab: the steps fit the chip, hold temporaries under
    one layer of the latent slab and move none of it; decode runs the
    latent kernel."""
    monkeypatch.setattr(ops, "pallas_mode", lambda: "tpu")
    model = build_model(MOONLIGHT.replace(experts_held=8))
    compiled, pages = _engine_step(model, one_chip, step, ML_SLOTS,
                                   ML_MAX_LEN)
    slab = pages["kv_pages"]
    layer = (math.prod(slab.shape) // MOONLIGHT.num_layers
             * slab.dtype.itemsize)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer, mem
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 0.85 * 16 * 2**30)
    text = compiled.as_text()
    assert ("latent_decode_paged" in text) == (step == "decode")
    assert _large_moves(text, layer) == []


_KERNEL_OPERANDS = re.compile(
    r"^\s*%latent_decode_paged\S* = .* custom-call\(.*"
    r"operand_layout_constraints=\{((?:\w+\[[\d,]*\]\{[^}]*\}(?:, )?)+)\}",
    re.M)


def test_latent_decode_takes_the_walkers_operands(one_chip, monkeypatch):
    """In Moonlight's compiled decode step, every ``latent_decode_paged``
    call takes the scalar operands ``flash_decode_paged`` takes, the
    layer, the lengths and the table (``s32[1]``, ``s32[slots]``,
    ``s32[slots, M]``), and no other: the kernel builds its (slot, block)
    work list itself, so no cumulative sum over the lengths (a
    ``reduce-window``) runs in the step."""
    monkeypatch.setattr(ops, "pallas_mode", lambda: "tpu")
    model = build_model(MOONLIGHT.replace(experts_held=8))
    compiled, _ = _engine_step(model, one_chip, "decode", ML_SLOTS,
                               ML_MAX_LEN)
    text = compiled.as_text()
    calls = _KERNEL_OPERANDS.findall(text)
    assert calls
    M = ML_MAX_LEN // PAGE
    for operands in calls:
        scalars = [(dt, dims) for dt, dims in
                   re.findall(r"(\w+)\[([\d,]*)\]", operands)
                   if dt.startswith(("s", "u"))]
        assert scalars == [("s32", "1"), ("s32", str(ML_SLOTS)),
                           ("s32", f"{ML_SLOTS},{M}")], operands
    assert "reduce-window" not in text
