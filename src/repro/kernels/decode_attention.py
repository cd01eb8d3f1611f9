"""Pallas flash-decode: one query token per sequence vs a KV cache.

TPU adaptation of flash-decoding: the kv-block axis is the sequential
inner grid dimension and the online-softmax state of every query head
rides in VMEM scratch across kv blocks (GPU flash-decode's split-k +
cross-SM reduction becomes grid-sequential accumulation — there is no
shared-memory combine step to port).

Layout. The grid is ``(B, n_blocks)``: one row per sequence. Each K/V
block holds ``block_k`` positions of ALL kv heads, ``[block_k, K, hd]``,
so the block's last two dims equal the cache's and the TPU tiling rule
holds for every K and hd; the cache is read in place, in the model's
layout, with no per-call transpose. Queries travel group-major,
``[G, K, hd]`` (query head ``h = kv * G + g``), so group ``g``'s rows
line up with the kv heads of the block. Scores are a broadcast multiply
and a lane reduction on the VPU: decode is bound by reading the cache,
and per-head 1-row matmuls (G == 1 for MHA) would leave the MXU idle
anyway.

Per-sequence ``lengths`` arrive by scalar prefetch (SMEM), before the
body runs. The K/V index map clamps block ``j`` to the sequence's last
valid block, so blocks past the length are neither computed (``pl.when``)
nor fetched again (an unchanged block index skips the copy). Rows with
``lengths == 0`` emit zeros: the accumulator never runs.

:func:`flash_decode_paged` is the same kernel gathering K/V through a
per-sequence **page table**: a block is one page, ``[page, K, hd]``, and
the index map resolves block ``j`` of sequence ``b`` to slab page
``page_table[b, j]`` (also scalar-prefetched), so the page indirection
costs no extra DMA step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 128
#: Largest ``block_k * G * K`` (K rounded up to the 8-row f32 tile) whose
#: f32 working set fits scoped VMEM. Measured by compiling
#: ``flash_decode_paged`` for v5e at the repo's configs' widths, hd <= 128
#: (largest page that compiled, first that did not): K=16 G=1 1024/2048,
#: K=20 G=1 1024/2048, K=32 G=1 512/1024, K=36 G=1 512/1024, K=8 G=2
#: and G=4 512/1024, K=8 G=8 256/512. The bound admits no page above the
#: largest that compiled at each width; wider heads are unmeasured.
MAX_BLOCK_ROWS = 8192


def check_page_size(page_size: int, num_heads: int,
                    num_kv_heads: int) -> None:
    """Raise ValueError unless ``flash_decode_paged`` can take this page:
    a page is one kernel block ``[page, K, hd]``, bounded only by VMEM."""
    rows = (num_heads // num_kv_heads) * (-(-num_kv_heads // 8) * 8)
    limit = MAX_BLOCK_ROWS // rows
    if not 1 <= page_size <= limit:
        raise ValueError(
            f"page_size={page_size} is not a legal paged-decode block for "
            f"{num_heads} query / {num_kv_heads} kv heads: use 1..{limit}")


def _last_block(length, block_k: int):
    """Index of the last block holding a valid position (0 when empty)."""
    return jnp.maximum((length + block_k - 1) // block_k - 1, 0)


def _decode_kernel(*refs, n_prefetch: int, block_k: int, sm_scale: float):
    len_ref = refs[0]
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs[n_prefetch:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]
    G = q_ref.shape[1]

    @pl.when(j == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_k < length)
    def compute():
        k = k_ref[0].astype(jnp.float32)                   # [bk, K, D]
        v = v_ref[0].astype(jnp.float32)
        pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (k.shape[0], k.shape[1], 1), 0)
        valid = pos < length                               # [bk, K, 1]
        for g in range(G):
            q = q_ref[0, g].astype(jnp.float32) * sm_scale  # [K, D]
            s = jnp.sum(k * q[None], axis=-1, keepdims=True)  # [bk, K, 1]
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_scr[g]                              # [K, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[None])                   # [bk, K, 1]
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=0)
            acc_scr[g] = acc_scr[g] * alpha + jnp.sum(p * v, axis=0)
            m_scr[g] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def emit():
        l = jnp.maximum(l_scr[...], 1e-30)                 # [G, K, 1]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _decode_call(q, k, v, prefetch, kv_map, n_blocks: int, block_k: int,
                 sm_scale: Optional[float], interpret: bool) -> jax.Array:
    """Shared pallas_call: ``k``/``v`` are tiled in ``[1, block_k, K, D]``
    blocks placed by ``kv_map(b, j, *prefetch_refs)``."""
    B, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = sm_scale if sm_scale is not None else D ** -0.5
    qg = jnp.swapaxes(q.reshape(B, K, G, D), 1, 2)        # [B, G, K, D]

    def qo_map(b, j, *_):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_blocks),
        in_specs=[pl.BlockSpec((1, G, K, D), qo_map),
                  pl.BlockSpec((1, block_k, K, D), kv_map),
                  pl.BlockSpec((1, block_k, K, D), kv_map)],
        out_specs=pl.BlockSpec((1, G, K, D), qo_map),
        scratch_shapes=[pltpu.VMEM((G, K, 1), jnp.float32),
                        pltpu.VMEM((G, K, 1), jnp.float32),
                        pltpu.VMEM((G, K, D), jnp.float32)],
    )
    kernel = functools.partial(_decode_kernel, n_prefetch=len(prefetch),
                               block_k=block_k, sm_scale=scale)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, K, D), q.dtype),
        interpret=interpret,
    )(*prefetch, qg, k, v)
    return jnp.swapaxes(o, 1, 2).reshape(B, H, D)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 lengths: jax.Array, *, sm_scale: Optional[float] = None,
                 block_k: int = DEFAULT_BLOCK_K,
                 interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; caches [B, S, K, D]; lengths [B] -> [B, H, D]."""
    S = k_cache.shape[1]
    block_k = min(block_k, S)
    pad = (-S) % block_k
    if pad:  # masked by lengths
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)

    def kv_map(b, j, lens):
        return (b, jnp.minimum(j, _last_block(lens[b], block_k)), 0, 0)

    return _decode_call(q, k_cache, v_cache, (lengths.astype(jnp.int32),),
                        kv_map, (S + pad) // block_k, block_k, sm_scale,
                        interpret)


def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       page_table: jax.Array, lengths: jax.Array, *,
                       sm_scale: Optional[float] = None,
                       interpret: bool = False) -> jax.Array:
    """Flash-decode gathering K/V through a page table.

    q: [B, H, D]; k_pages, v_pages: [P, page_size, K, D] (shared slab,
    page 0 reserved as the null page); page_table: [B, M] int32;
    lengths: [B] -> [B, H, D]. Token ``t`` of sequence ``b`` lives at
    ``(page_table[b, t // page_size], t % page_size)``; table entries at
    or past ``ceil(lengths[b] / page_size)`` are never read.
    """
    page_size = k_pages.shape[1]

    def kv_map(b, j, lens, tbl):
        return (tbl[b, jnp.minimum(j, _last_block(lens[b], page_size))],
                0, 0, 0)

    return _decode_call(q, k_pages, v_pages,
                        (lengths.astype(jnp.int32),
                         page_table.astype(jnp.int32)),
                        kv_map, page_table.shape[1], page_size, sm_scale,
                        interpret)
