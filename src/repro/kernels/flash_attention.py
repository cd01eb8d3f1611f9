"""Pallas TPU flash attention (forward + backward), GQA-aware.

TPU adaptation notes (vs the CUDA FlashAttention the literature targets):
  * tiling is driven by BlockSpecs over (head, q-block, kv-block) grid —
    the kv axis is the innermost, sequential grid dimension, so the
    online-softmax running state (m, l, acc) lives in VMEM scratch that
    persists across kv steps; there is no cross-"block" shared memory.
  * tile shapes default to 512x512 with the head dim padded to a multiple
    of 128 (MXU lane width) by the wrapper; fp32 accumulation throughout.
    Sequence lengths that are not a multiple of the block are padded up
    to one: padded key columns are masked, padded query rows dropped.
  * the running max and sum ride in lane-replicated ``(block_q, 128)``
    scratch, and the row logsumexp the backward needs is written in the
    same ``(N, S, 128)`` layout: a rank-1 or one-row block is not a legal
    TPU tile. The saved residual keeps one lane of it.
  * causal masking skips whole blocks above the diagonal via pl.when
    (compute guard), and the kv index map is clamped to the diagonal
    block so skipped blocks are not fetched either.

Backward follows the standard two-kernel split: dKV iterates q-blocks per
kv-block, dQ iterates kv-blocks per q-block, both reusing the saved
row-logsumexp L = m + log(l).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 512
NEG_INF = -1e30
LANES = 128


def _pad_axis(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _last_kv_block(qi, *, causal, block_q, block_k, nk):
    """Last kv block that query block ``qi`` attends to."""
    if not causal:
        return nk - 1
    return jnp.minimum((qi * block_q + block_q - 1) // block_k, nk - 1)


def _mask(s, qi, ki, *, causal, block_q, block_k, kv_len):
    """Causal and key-padding masks on a ``[block_q, block_k]`` score
    tile. Kv block 0 always runs first and holds a live column for every
    row, so later fully-masked rows cannot poison the running max."""
    if not causal and kv_len is None:
        return s
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = cols < kv_len if kv_len is not None else None
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        keep = rows >= cols if keep is None else keep & (rows >= cols)
    return jnp.where(keep, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, sm_scale, causal,
                block_q, block_k, kv_len, logits_soft_cap):
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    last = _last_kv_block(qi, causal=causal, block_q=block_q,
                          block_k=block_k, nk=pl.num_programs(2))

    @pl.when(ki == 0)
    def init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki <= last)
    def compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale          # [bq, d]
        k = k_ref[0].astype(jnp.float32)                     # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        if logits_soft_cap is not None:
            s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
        s = _mask(s, qi, ki, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=kv_len)
        m_prev = m_scr[...]                                  # [bq, LANES]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot(p, v)
        m_scr[...] = m_new

    @pl.when(ki == last)
    def emit():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_scr[...] + jnp.log(l)


def _fwd_call(q, k, v, sm_scale, causal, block_q, block_k, kv_len,
              logits_soft_cap, interpret, save_lse):
    """Returns (o [N, S, D], lse [N, S, LANES] or None)."""
    N, S, D = q.shape
    NK, T = k.shape[0], k.shape[1]
    G = N // NK
    nq, nk = S // block_q, T // block_k
    last = functools.partial(_last_kv_block, causal=causal, block_q=block_q,
                             block_k=block_k, nk=nk)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=kv_len, logits_soft_cap=logits_soft_cap)

    def kv_map(h, i, j):
        return (h // G, jnp.minimum(j, last(i)), 0)

    out_specs = [pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((N, S, D), q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((1, block_q, LANES),
                                      lambda h, i, j: (h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((N, S, LANES), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(N, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_q, LANES), jnp.float32),
                        pltpu.VMEM((block_q, LANES), jnp.float32),
                        pltpu.VMEM((block_q, D), jnp.float32)],
        out_shape=out_shape,
        interpret=interpret,
    )(q, k, v)
    return out[0], (out[1] if save_lse else None)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, sm_scale, causal, block_q, block_k, kv_len):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    last = _last_kv_block(qi, causal=causal, block_q=block_q,
                          block_k=block_k, nk=pl.num_programs(1))

    @pl.when(qi == 0)
    def init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(ki <= last)
    def compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale          # [bq, d]
        k = k_ref[0].astype(jnp.float32)                     # [bk, d]
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)                   # [bq, d]
        lse = lse_ref[0][:, :1]                              # [bq, 1]
        delta = delta_ref[0][:, :1]                          # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = _mask(s, qi, ki, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=kv_len)
        p = jnp.exp(s - lse)                                 # [bq, bk]
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(qi == nq - 1)
    def emit():
        dk_ref[0] = (dk_scr[...] / sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, sm_scale, causal, block_q, block_k,
                   kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    last = _last_kv_block(qi, causal=causal, block_q=block_q,
                          block_k=block_k, nk=pl.num_programs(2))

    @pl.when(ki == 0)
    def init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(ki <= last)
    def compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        s = _mask(s, qi, ki, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=kv_len)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot(ds, k)

    @pl.when(ki == last)
    def emit():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k, kv_len,
         interpret):
    N, S, D = q.shape
    NK, T = k.shape[0], k.shape[1]
    G = N // NK
    nq, nk = S // block_q, T // block_k
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    lse = jnp.broadcast_to(lse[..., None], (N, S, LANES))
    delta = jnp.broadcast_to(delta[..., None], (N, S, LANES))
    kw = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
              block_k=block_k, kv_len=kv_len)

    # dKV accumulates over the q-heads of the group: run per (q-head) and
    # sum the G contributions outside the kernel.
    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **kw),
        grid=(N, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, j, i, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, j, i, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda h, j, i: (h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, j, i: (h, j, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((N, T, D), jnp.float32),
            jax.ShapeDtypeStruct((N, T, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk_per_head, dv_per_head = dkv
    dk = dk_per_head.reshape(NK, G, T, D).sum(axis=1).astype(k.dtype)
    dv = dv_per_head.reshape(NK, G, T, D).sum(axis=1).astype(v.dtype)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(N, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda h, i, j: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((N, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, kv_len,
           logits_soft_cap, interpret):
    o, _ = _fwd_call(q, k, v, sm_scale, causal, block_q, block_k, kv_len,
                     logits_soft_cap, interpret, save_lse=False)
    return o


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, kv_len,
               logits_soft_cap, interpret):
    o, lse = _fwd_call(q, k, v, sm_scale, causal, block_q, block_k, kv_len,
                       logits_soft_cap, interpret, save_lse=True)
    return o, (q, k, v, o, lse[..., 0])


def _flash_bwd(sm_scale, causal, block_q, block_k, kv_len, logits_soft_cap,
               interpret, res, do):
    if logits_soft_cap is not None:
        raise NotImplementedError("soft-cap backward not implemented")
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k,
                kv_len, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    logits_soft_cap: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK, block_k: int = DEFAULT_BLOCK,
                    interpret: bool = False) -> jax.Array:
    """q: [B, S, H, D]; k, v: [B, T, K, D] -> [B, S, H, D].

    S and T need not be multiples of the blocks. Causal attention takes
    ``S == T`` (query ``i`` sees keys ``0..i``)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    if causal and S != T:
        raise ValueError(f"causal flash attention needs S == T, got {S}, {T}")
    scale = sm_scale if sm_scale is not None else D ** -0.5
    block_q, block_k = min(block_q, S), min(block_k, T)
    # fold batch & heads; pad the head dim to the MXU lane width and the
    # sequences to whole blocks
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, D)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * K, T, D)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * K, T, D)
    qf = _pad_axis(_pad_axis(qf, 2, LANES), 1, block_q)
    kf = _pad_axis(_pad_axis(kf, 2, LANES), 1, block_k)
    vf = _pad_axis(_pad_axis(vf, 2, LANES), 1, block_k)
    kv_len = T if kf.shape[1] != T else None
    o = _flash(qf, kf, vf, scale, causal, block_q, block_k, kv_len,
               logits_soft_cap, interpret)
    o = o[:, :S, :D].reshape(B, H, S, D)
    return jnp.moveaxis(o, 1, 2)
