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

The two paged kernels read the serving slab ``[L, P, page, lanes]``
through a per-sequence **page table**, whole: it stays in HBM, and the
layer's index, the lengths and the table arrive by scalar prefetch.
Both run one walk (``_walk_live_blocks``) whose time follows the live
positions, not slots x max_len: one grid step first builds, on the
scalar core, the list of (slot, block) pairs whose block of ``T``
positions (``paged_block_rows``) holds a live position, then walks it.
Each block's live pages are copied from HBM into a VMEM double buffer
by async copies started one block ahead; a page at or past
``ceil(length / page)`` is never copied. The online-softmax state is
reset on a slot's first block and emitted on its last. The kernels
differ only in how they score a block:

- :func:`flash_decode_paged` (GQA/MHA) reads K and V from two slabs
  ``[L, P, page, K * hd]``. The queries sit block-diagonally on the
  slab's lanes (``[H, K * hd]``, each head's query in its kv head's
  lanes, zeros elsewhere), so one MXU product scores every head of a
  block and one more weights its values. Pages that are not whole 8-row
  tiles cannot be sliced out of the slab by an async copy; they take the
  same arithmetic over a ``(B, M)`` grid of single pages fetched by the
  grid's pipeline, whose dead steps copy nothing but still cost a grid
  step each.
- :func:`latent_decode_paged` (latent attention, MLA) reads one slab
  ``[L, P, page, C]`` of latent rows, each the key of every head (all
  ``C`` lanes) and its value (the first ``v_dim``): one product scores
  all heads of a block.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 128
#: Largest ``page * K * hd`` (``K * hd`` rounded up to 128 lanes) that
#: ``check_page_size`` admits, and the most elements each double buffer of
#: the walk over live blocks (K and V, or the latent rows) holds where the
#: page allows (``paged_block_rows``). The page bound was measured by compiling
#: the earlier per-page kernel for v5e, whose f32 working set it kept in
#: scoped VMEM (largest page that compiled, first that did not): K*hd 1024
#: at G = 1, 2, 4 1024/2048; K*hd 2560 at G = 1 512/768; K*hd 2304 at
#: G = 1 512/1024; K*hd 896 at G = 8 1024 compiled. The largest page it
#: admits at each compile-tested width still compiles.
MAX_BLOCK_ELEMS = 1 << 20


def check_page_size(page_size: int, num_kv_heads: int,
                    head_dim: int) -> None:
    """Raise ValueError unless ``flash_decode_paged`` can take this page:
    a page is one kernel block ``[page, K * hd]``, bounded only by VMEM."""
    lanes = -(-num_kv_heads * head_dim // 128) * 128
    limit = MAX_BLOCK_ELEMS // lanes
    if not 1 <= page_size <= limit:
        raise ValueError(
            f"page_size={page_size} is not a legal block of "
            f"flash_decode_paged for {num_kv_heads} kv heads of "
            f"{head_dim}: use 1..{limit}")


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


def _dot_f32(a, b, dims):
    """``a . b`` with an f32 result: exact products of bf16 operands, all
    passes (HIGHEST) for f32 ones."""
    hi = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, precision=hi,
                               preferred_element_type=jnp.float32)


def _mm_f32(a, b, dims):
    """``a . b`` in f32 for an f32 ``a`` and ``b`` exact in its own dtype.
    A bf16 ``b`` takes ``a`` split into two bf16 terms (hi + lo), which
    keeps ``a`` to about 16 bits where one bf16 pass would round it to
    8."""
    dot = functools.partial(_dot_f32, dims=dims)
    if b.dtype == jnp.float32:
        return dot(a, b)
    a_hi = a.astype(b.dtype)
    a_lo = (a - a_hi.astype(jnp.float32)).astype(b.dtype)
    return dot(a_hi, b) + dot(a_lo, b)


#: Most positions of one slot that one step of the walk over live blocks
#: attends (``paged_block_rows``).
PAGED_BLOCK = 512


def paged_block_rows(page_size: int, lanes: int) -> int:
    """``T``: the positions of one slot that one step of the walk over
    live blocks attends, ``T // page_size`` pages of a slab of ``lanes``
    lanes copied together into one half of a double buffer. At most
    ``PAGED_BLOCK``, and small enough that each double buffer holds at
    most ``MAX_BLOCK_ELEMS`` (512 rows at 1,024 lanes); a multiple of 128
    where the page allows, so that the scores fill whole lane tiles. A
    page that is not a multiple of 16 rows (a bf16 tile), or that does
    not fit that bound, is a block of its own."""
    lanes = -(-lanes // 128) * 128
    rows = min(PAGED_BLOCK, MAX_BLOCK_ELEMS // (2 * lanes))
    if page_size % 16 or page_size >= rows:
        return page_size
    unit = math.lcm(page_size, 128)
    if unit > rows:
        unit = page_size
    return rows // unit * unit


def _block_rows(G: int, K: int, head_dim: int):
    """``(group, own)``, each ``[G * K, K * head_dim]``: query row ``r =
    g * K + kv`` (query head ``kv * G + g``) is of group ``g`` and owns
    the lanes of kv head ``kv``."""
    H, KD = G * K, K * head_dim
    row = jax.lax.broadcasted_iota(jnp.int32, (H, KD), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (H, KD), 1)
    group = sum((row >= g * K).astype(jnp.int32) for g in range(1, G))
    first = (row - group * K) * head_dim
    return group, (lane >= first) & (lane < first + head_dim)


def _fold_block(carry, s, values, pos0, length):
    """Fold one block of positions ``pos0 ..`` into the online-softmax
    state ``carry = (m, l, acc)`` of all query rows: scores ``s`` [H, T],
    scaled, and values [T, V]; positions at or past ``length`` weigh
    nothing. The weights take the values in f32 (``_mm_f32``)."""
    m, l, acc = carry
    T = s.shape[1]
    pos = pos0 + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    s = jnp.where(pos < length, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)                                 # [H, T]
    l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
    # rows past the length hold whatever the buffer held: zero them,
    # since a zero weight times a stale NaN is still NaN
    col = pos0 + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
    v = jnp.where(col < length, values, 0)
    acc = acc * alpha + _mm_f32(p, v, (((1,), (0,)), ((), ())))
    return m_new, l, acc


def _attend_block(q_ref, b, keys, values, pos0, length, carry, rows, *,
                  sm_scale: float):
    """One block of rows (positions ``pos0 ..``) of sequence ``b`` into
    the online-softmax state ``carry = (m, l, acc)`` of all query rows.
    The queries sit block-diagonally on the slab's lanes (row ``r`` holds
    its query in its kv head's lanes, zeros elsewhere), so one product
    scores every head: exact products of the inputs, summed in f32.
    ``rows`` is ``_block_rows``'s ``(group, own)``."""
    group, own = rows
    G = q_ref.shape[1]
    q_rows = q_ref[b, 0].astype(jnp.float32)               # [1, KD]
    for g in range(1, G):
        q_rows = jnp.where(group == g, q_ref[b, g].astype(jnp.float32),
                           q_rows)
    dt = jnp.promote_types(q_ref.dtype, keys.dtype)
    q_bd = jnp.where(own, q_rows, 0.0).astype(dt)
    s = _dot_f32(q_bd, keys.astype(dt),
                 (((1,), (1,)), ((), ()))) * sm_scale       # [H, T]
    return _fold_block(carry, s, values, pos0, length)


def _emit_rows(o_ref, b, acc, l, rows):
    """Output row ``b``, ``[G, 1, KD]``: each query row's normalised
    values on its own kv head's lanes, summed over the rows of a group."""
    group, own = rows
    out = jnp.where(own, acc / jnp.maximum(l, 1e-30), 0.0)
    for g in range(o_ref.shape[1]):
        o_ref[b, g] = jnp.sum(jnp.where(group == g, out, 0.0), axis=0,
                              keepdims=True).astype(o_ref.dtype)


def _walk_live_blocks(layer_ref, len_ref, tbl_ref, srcs, o_ref, buf, sem,
                      slot_ref, blk_ref, *, acc_shape, attend, emit):
    """The walk of both paged decode kernels over the live blocks of a
    batch, in one grid step.

    srcs: the HBM slabs ``[L, P, page, lanes]`` read at layer
    ``layer_ref[0]`` through the table (``(k, v)``, or the latent slab
    alone); buf: their VMEM double buffer ``[len(srcs), 2, T, lanes]``,
    sem its two DMA semaphores; slot_ref, blk_ref: SMEM work list. First,
    on the scalar core, the (slot, block) pairs whose block of ``T``
    positions holds a live position, slot by slot, block by block. Then
    for each pair: the block's live pages copied into one half of the
    buffer (started one pair ahead; a page at or past ``ceil(length /
    page)`` is never copied); the online-softmax state ``(m, l, acc)``,
    ``acc`` of ``acc_shape``, reset on the slot's first block; ``attend(b,
    pos0, blocks, length, carry) -> carry`` with ``blocks`` the sources'
    ``[T, lanes]`` rows of positions ``pos0 ..``; and ``emit(b, carry)``
    on its last block. The output is zeroed first: a slot of length 0
    emits zeros."""
    B, M = tbl_ref.shape
    page, T = srcs[0].shape[2], buf.shape[2]
    ppb = T // page
    layer = layer_ref[0]
    o_ref[...] = jnp.zeros_like(o_ref)

    def add_slot(b, n):
        def add(j, n):
            slot_ref[n] = b
            blk_ref[n] = j
            return n + 1
        return jax.lax.fori_loop(0, (len_ref[b] + T - 1) // T, add, n)

    n_items = jax.lax.fori_loop(0, B, add_slot, 0)

    def copies(item, half):
        """Per page of work item ``item``: whether it holds a live
        position, and its copies, one from each source, into buffer
        ``half``."""
        b = slot_ref[item]
        out = []
        for i in range(ppb):
            j = blk_ref[item] * ppb + i
            pid = tbl_ref[b, jnp.minimum(j, M - 1)]
            out.append((j * page < len_ref[b], [
                pltpu.make_async_copy(src.at[layer, pid],
                                      buf.at[c, half, pl.ds(i * page, page)],
                                      sem.at[half])
                for c, src in enumerate(srcs)]))
        return out

    def start(item, half):
        for live, cps in copies(item, half):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.start()

    def wait(item, half):
        for live, cps in copies(item, half):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.wait()

    @pl.when(n_items > 0)
    def _():
        start(0, 0)

    def body(item, carry):
        half = item % 2

        @pl.when(item + 1 < n_items)
        def _():
            start(item + 1, 1 - half)

        wait(item, half)
        b, blk = slot_ref[item], blk_ref[item]
        length = len_ref[b]
        fresh = blk == 0
        carry = tuple(jnp.where(fresh, x0, x) for x0, x in zip(init, carry))
        carry = attend(b, blk * T, [buf[c, half] for c in range(len(srcs))],
                       length, carry)

        @pl.when((blk + 1) * T >= length)
        def _():
            emit(b, carry)

        return carry

    H = acc_shape[0]
    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros(acc_shape, jnp.float32))
    jax.lax.fori_loop(0, n_items, body, init)


def _walk_spec(q_shape, out_shape, srcs, M: int):
    """The grid spec of a kernel over ``_walk_live_blocks``: one grid
    step, the queries and the output whole in VMEM, the slabs ``srcs``
    left in HBM; as scratch their double buffer of ``paged_block_rows``
    positions, its DMA semaphores and the work list, with room for every
    block of every slot's ``M`` pages."""
    _, _, page, lanes = srcs[0].shape
    T = paged_block_rows(page, lanes)
    n_max = q_shape[0] * -(-M * page // T)

    def whole(*_):
        return (0,) * len(q_shape)

    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec(q_shape, whole)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(srcs),
        out_specs=pl.BlockSpec(out_shape, whole),
        scratch_shapes=[pltpu.VMEM((len(srcs), 2, T, lanes), srcs[0].dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((n_max,), jnp.int32),
                        pltpu.SMEM((n_max,), jnp.int32)])


def _paged_kernel(layer_ref, len_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref,
                  buf, sem, slot_ref, blk_ref, *, head_dim: int,
                  sm_scale: float):
    G, KD = q_ref.shape[1], q_ref.shape[3]
    K = KD // head_dim
    rows = _block_rows(G, K, head_dim)

    def attend(b, pos0, blocks, length, carry):
        keys, values = blocks
        return _attend_block(q_ref, b, keys, values, pos0, length, carry,
                             rows, sm_scale=sm_scale)

    def emit(b, carry):
        _, l, acc = carry
        _emit_rows(o_ref, b, acc, l, rows)

    _walk_live_blocks(layer_ref, len_ref, tbl_ref, (k_hbm, v_hbm), o_ref,
                      buf, sem, slot_ref, blk_ref, acc_shape=(G * K, KD),
                      attend=attend, emit=emit)


def _paged_grid_kernel(layer_ref, len_ref, tbl_ref, q_ref, k_ref, v_ref,
                       o_ref, m_scr, l_scr, acc_scr, *, head_dim: int,
                       sm_scale: float):
    """The same attention over a ``(B, M)`` grid of single pages fetched
    by the grid's own pipeline, for pages that are not whole 8-row tiles
    (which an async copy cannot slice out of the slab)."""
    del layer_ref, tbl_ref  # read by the index maps only
    b, j = pl.program_id(0), pl.program_id(1)
    page = k_ref.shape[2]
    length = len_ref[b]
    G, KD = q_ref.shape[1], q_ref.shape[3]
    rows = _block_rows(G, KD // head_dim, head_dim)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * page < length)
    def _():
        carry = _attend_block(q_ref, 0, k_ref[0, 0], v_ref[0, 0], j * page,
                              length, (m_scr[...], l_scr[...], acc_scr[...]),
                              rows, sm_scale=sm_scale)
        m_scr[...], l_scr[...], acc_scr[...] = carry

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        _emit_rows(o_ref, 0, acc_scr[...], l_scr[...], rows)


def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       layer, page_table: jax.Array, lengths: jax.Array, *,
                       sm_scale: Optional[float] = None,
                       interpret: bool = False) -> jax.Array:
    """Flash-decode of one layer, gathering K/V through a page table.

    q: [B, H, D]; k_pages, v_pages: [L, P, page_size, K * D] (the whole
    serving slab, page 0 of every layer reserved as the null page);
    layer: int32 scalar, the layer to read; page_table: [B, M] int32;
    lengths: [B] -> [B, H, D]. Head ``kv`` of token ``t`` of sequence
    ``b`` lives at ``[layer, page_table[b, t // page_size], t %
    page_size, kv * D:(kv + 1) * D]``; table entries at or past
    ``ceil(lengths[b] / page_size)`` are never read. Query head ``h``
    reads kv head ``h // (H // K)``. Scores are exact products of the
    inputs summed in f32, the softmax is f32; rows with ``lengths == 0``
    emit zeros.
    """
    B, H, D = q.shape
    _, _, page, KD = k_pages.shape
    K = KD // D
    G = H // K
    M = page_table.shape[1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    # group-major queries on the slab's lanes: qg[b, g, 0, kv * D + d]
    # is query head kv * G + g
    qg = jnp.swapaxes(q.reshape(B, K, G, D), 1, 2).reshape(B, G, 1, KD)
    if page % 8:
        def kv_map(b, j, lyr, lens, tbl):
            # an empty row reads the null page, not its dead table entry
            pid = tbl[b, jnp.minimum(j, _last_block(lens[b], page))]
            return (lyr[0], jnp.where(lens[b] > 0, pid, 0), 0, 0)

        def row_map(b, j, *_):
            return (b, 0, 0, 0)

        kernel = functools.partial(_paged_grid_kernel, head_dim=D,
                                   sm_scale=scale)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, M),
            in_specs=[pl.BlockSpec((1, G, 1, KD), row_map),
                      pl.BlockSpec((1, 1, page, KD), kv_map),
                      pl.BlockSpec((1, 1, page, KD), kv_map)],
            out_specs=pl.BlockSpec((1, G, 1, KD), row_map),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, KD), jnp.float32)])
    else:
        kernel = functools.partial(_paged_kernel, head_dim=D,
                                   sm_scale=scale)
        grid_spec = _walk_spec(qg.shape, qg.shape, (k_pages, v_pages), M)
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, 1, KD), q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="flash_decode_paged",
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      page_table.astype(jnp.int32), qg, k_pages, v_pages)
    return jnp.swapaxes(o.reshape(B, G, K, D), 1, 2).reshape(B, H, D)


def check_latent_page_size(page_size: int) -> None:
    """Raise ValueError unless ``latent_decode_paged`` can take this
    page: a multiple of 16 rows (a bf16 tile, so that each page's copy
    lands on whole tiles of the block buffer) that divides
    ``PAGED_BLOCK``, 512."""
    if not (page_size >= 16 and page_size % 16 == 0
            and PAGED_BLOCK % page_size == 0):
        raise ValueError(
            f"page_size={page_size} is not a legal block of "
            f"latent_decode_paged: use a multiple of 16 that divides "
            f"{PAGED_BLOCK}")


def _latent_kernel(layer_ref, len_ref, tbl_ref, q_ref, slab_hbm, o_ref, buf,
                   sem, slot_ref, blk_ref, *, v_dim: int, sm_scale: float):
    def attend(b, pos0, blocks, length, carry):
        rows, = blocks                                     # [T, C]
        s = _dot_f32(q_ref[b], rows,
                     (((1,), (1,)), ((), ()))) * sm_scale    # [H, T]
        return _fold_block(carry, s, rows[:, :v_dim], pos0, length)

    def emit(b, carry):
        _, l, acc = carry
        o_ref[b] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    _walk_live_blocks(layer_ref, len_ref, tbl_ref, (slab_hbm,), o_ref, buf,
                      sem, slot_ref, blk_ref,
                      acc_shape=(q_ref.shape[1], v_dim), attend=attend,
                      emit=emit)


def latent_decode_paged(q: jax.Array, kv_pages: jax.Array, layer,
                        page_table: jax.Array, lengths: jax.Array, *,
                        sm_scale: float, v_dim: int,
                        interpret: bool = False) -> jax.Array:
    """Latent (MLA) decode attention of one layer through a page table.

    q: [B, H, C], the absorbed queries; kv_pages: [L, P, page, C], the
    whole latent slab (page 0 of every layer the null page); layer:
    int32 scalar; page_table: [B, M] int32; lengths: [B] -> [B, H,
    v_dim]. Row ``t`` of sequence ``b`` lives at ``[layer, page_table[b,
    t // page], t % page]``; it is the key of every head and its first
    ``v_dim`` lanes the value. Scores ``q . row * sm_scale`` in f32 (one
    MXU product for all heads of a block), f32 online softmax; rows with
    ``lengths == 0`` emit zeros. Only live pages are copied.
    """
    B, H, _ = q.shape
    check_latent_page_size(kv_pages.shape[2])
    out = (B, H, v_dim)
    kernel = functools.partial(_latent_kernel, v_dim=v_dim,
                               sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid_spec=_walk_spec(q.shape, out, (kv_pages,), page_table.shape[1]),
        out_shape=jax.ShapeDtypeStruct(out, q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="latent_decode_paged",
    )(jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32),
      page_table.astype(jnp.int32), q, kv_pages)
