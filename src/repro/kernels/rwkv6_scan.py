"""Pallas RWKV6 WKV scan: VMEM-resident recurrent state.

TPU adaptation: the GPU implementations (flash-linear-attention CUDA)
tile the recurrence over warps with shared-memory staging. On TPU the
win is different — the [D, D] per-head state lives in VMEM *scratch*
across the whole sequence (grid-sequential chunk axis), so HBM traffic is
exactly r/k/v/w streamed once plus the output, instead of a state
round-trip per step. The per-step update is a rank-1 outer product +
elementwise decay (VPU work); the chunk loop is unrolled at compile time.

RWKV6's decay is *per-channel per-step* (a vector, not a scalar), which
breaks the matmul-form chunking usable for Mamba-2 (see mamba2_scan.py);
a DPLR-style matrix chunking exists but is out of scope — documented in
DESIGN.md.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                o_ref, sT_ref, s_scr, *, chunk):
    j = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(j == 0)
    def init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)      # [chunk, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)      # [D]

    s = s_scr[...]                        # [D, D]
    outs = []
    for t in range(chunk):                # static unroll: VREG-friendly
        kv = k[t][:, None] * v[t][None, :]            # [D, D]
        outs.append((r[t][:, None] * (s + u[:, None] * kv)).sum(axis=0))
        s = w[t][:, None] * s + kv
    s_scr[...] = s
    o_ref[0] = jnp.stack(outs).astype(o_ref.dtype)

    @pl.when(j == nc - 1)
    def emit_state():
        sT_ref[0] = s_scr[...].astype(sT_ref.dtype)


def rwkv6_chunked(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                  u: jax.Array, state: Optional[jax.Array] = None, *,
                  chunk: int = DEFAULT_CHUNK, interpret: bool = False
                  ) -> Tuple[jax.Array, jax.Array]:
    """Same contract as ref.rwkv6_scan: r/k/v/w [B,S,H,D], u [H,D],
    state [B,H,D,D] -> (out [B,S,H,D], state [B,H,D,D])."""
    B, S, H, D = r.shape
    if state is None:
        state = jnp.zeros((B, H, D, D), jnp.float32)
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    nc = S // chunk

    def fold(x):  # [B,S,H,D] -> [B*H, S, D]
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, D)

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    s0 = state.reshape(B * H, D, D)

    kernel = functools.partial(_wkv_kernel, chunk=chunk)
    scratch = [pltpu.VMEM((D, D), jnp.float32)]
    o, sT = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, D), lambda i, j, H=H: (i % H, 0)),
            pl.BlockSpec((1, D, D), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, D, D), lambda i, j: (i, 0, 0)),
        ],
        scratch_shapes=scratch,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), r.dtype),
            jax.ShapeDtypeStruct((B * H, D, D), jnp.float32),
        ],
        interpret=interpret,
    )(rf, kf, vf, wf, u, s0)
    out = jnp.moveaxis(o.reshape(B, H, S, D), 1, 2)
    return out, sT.reshape(B, H, D, D)
