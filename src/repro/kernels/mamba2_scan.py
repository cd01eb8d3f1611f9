"""Pallas Mamba-2 SSD chunked scan (matmul-form, MXU-friendly).

TPU adaptation of the SSD algorithm: Mamba-2's scalar-per-head decay
admits an exact chunk-parallel form where intra-chunk work is two
[T, T] x [T, P/N] matmuls (MXU) and only the [P, N] chunk-boundary state
recurses — carried in VMEM scratch across the sequential chunk axis of
the grid, never round-tripping HBM. Decay factors use cumulative log
space; all exponents are <= 0, so no rescaling is needed.

    cum[t]   = sum_{r<=t} a*dt[r]                     (per chunk)
    L[t,s]   = exp(cum[t]-cum[s]) for t>=s else 0
    y_intra  = ((C B^T) o L) @ (dt*x)
    y_inter  = exp(cum) * (C @ h_prev^T)
    h_next   = exp(cum[-1]) h_prev + (dt*x * exp(cum[-1]-cum))^T @ B
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                y_ref, hT_ref, h_scr, *, chunk):
    j = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(j == 0)
    def init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # [T, P]
    dt = dt_ref[0].astype(jnp.float32)        # [T]
    a = a_ref[0].astype(jnp.float32)          # scalar (this head)
    b = b_ref[0].astype(jnp.float32)          # [T, N]
    c = c_ref[0].astype(jnp.float32)          # [T, N]
    h = h_scr[...]                            # [P, N]

    cum = jnp.cumsum(a * dt)                  # [T], <= 0
    # intra-chunk: scores[t,s] = (c_t . b_s) * exp(cum[t]-cum[s]) (t>=s)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))  # [T, T]
    decay = jnp.exp(cum[:, None] - cum[None, :])
    T = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
    scores = jnp.where(rows >= cols, scores * decay, 0.0)
    xdt = x * dt[:, None]                     # [T, P]
    y = jax.lax.dot(scores, xdt)              # [T, P]
    # inter-chunk: y += exp(cum) * (c @ h^T)
    y = y + jnp.exp(cum)[:, None] * jax.lax.dot_general(
        c, h, (((1,), (1,)), ((), ())))       # [T, P]
    # boundary state update
    w = jnp.exp(cum[-1] - cum)                # [T]
    h_scr[...] = (jnp.exp(cum[-1]) * h +
                  jax.lax.dot_general(xdt * w[:, None], b,
                                      (((0,), (0,)), ((), ()))))  # [P, N]
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(j == nc - 1)
    def emit_state():
        hT_ref[0] = h_scr[...].astype(hT_ref.dtype)


def mamba2_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, state: Optional[jax.Array] = None, *,
                   chunk: int = DEFAULT_CHUNK, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    """Same contract as ref.mamba2_scan: x [B,S,H,P], dt [B,S,H], a [H],
    b/c [B,S,N], state [B,H,P,N] -> (y [B,S,H,P], state)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    if state is None:
        state = jnp.zeros((B, H, P, N), jnp.float32)
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} not divisible by chunk={chunk}")
    nc = S // chunk

    xf = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(B * H, S)
    bf = jnp.repeat(b[:, None], H, axis=1).reshape(B * H, S, N)
    cf = jnp.repeat(c[:, None], H, axis=1).reshape(B * H, S, N)
    h0 = state.reshape(B * H, P, N)
    af = jnp.tile(a, B)                       # [B*H]

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    scratch = [pltpu.VMEM((P, N), jnp.float32)]
    y, hT = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (i,)),
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, N), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, P, N), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, P, N), lambda i, j: (i, 0, 0)),
        ],
        scratch_shapes=scratch,
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(xf, dtf, af, bf, cf, h0)
    out = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    return out, hT.reshape(B, H, P, N)
