#!/usr/bin/env python3
"""Control readings for ``chip_smoke.py``'s kernel tolerance.

    python scripts/kernel_controls.py [--seed 0]

On the smoke's inputs (``chip_smoke.kernel_inputs``) it reads the error
beyond bf16 output rounding, as the smoke does (``kernel_error``), of
the sound kernels and of deliberately degraded attentions:

- admitted: decode with bf16 matmul operands (``ref.decode_attention``
  on the bf16 cache rounds the softmax probabilities to bf16 before
  p.v);
- rejected: an online softmax whose accumulator or running sum is
  rounded to bf16 after every block, the last position of each decode
  row dropped, one future key let into the causal prefill;
- only read: the prefill with its probabilities rounded to bf16, which
  sits at ``PREFILL_TOL``.

It exits non-zero unless every sound and admitted reading is within its
kernel's limit (``chip_smoke.DECODE_TOL`` or ``PREFILL_TOL``) and every
rejected one is beyond it. The
kernels run compiled on a TPU and in interpret mode elsewhere; the
controls are plain jnp. Rounding is emulated with
``lax.reduce_precision``, which XLA keeps, where an f32->bf16->f32
convert pair may be folded away.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import chip_smoke as smoke  # noqa: E402


def bf16_round(x):
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def online_attention(s, v, block: int, *, acc_bf16=False, l_bf16=False):
    """Online-softmax attention of masked scores ``s [G, R, T]`` over
    values ``v [G, T, D]`` in blocks of ``block`` keys, in f32 unless the
    accumulator or the running sum is rounded to bf16 after each block.
    A row whose scores are all masked comes out 0."""
    import jax
    import jax.numpy as jnp

    G, R, T = s.shape
    pad = -T % block
    s = jnp.pad(s, ((0, 0), (0, 0), (0, pad)), constant_values=-jnp.inf)
    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    n = (T + pad) // block
    s = s.reshape(G, R, n, block).transpose(2, 0, 1, 3)
    v = v.reshape(G, n, block, -1).transpose(1, 0, 2, 3)

    def step(carry, blk):
        m, l, acc = carry
        sj, vj = blk
        m_new = jnp.maximum(m, sj.max(-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(m - m_safe)
        p = jnp.exp(sj - m_safe[..., None])
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "grt,gtd->grd", p, vj, precision="highest")
        if l_bf16:
            l = bf16_round(l)
        if acc_bf16:
            acc = bf16_round(acc)
        return (m_new, l, acc), None

    init = (jnp.full((G, R), -jnp.inf), jnp.zeros((G, R)),
            jnp.zeros((G, R, v.shape[-1])))
    (_, l, acc), _ = jax.lax.scan(step, init, (s, v))
    return acc / jnp.where(l > 0, l, 1.0)[..., None]


def decode_scores(q, k_cache, lengths):
    """Masked f32 scores ``[B*H, 1, T]`` of one query per slot and head
    (H == K here)."""
    import jax.numpy as jnp

    B, H, D = q.shape
    s = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32) * D ** -0.5,
                   k_cache.astype(jnp.float32), precision="highest")
    valid = jnp.arange(k_cache.shape[1])[None, None] < lengths[:, None, None]
    return jnp.where(valid, s, -jnp.inf).reshape(B * H, 1, -1)


def prefill_scores(q, k, shift: int = 0):
    """Causal f32 scores ``[H, S, T]``; ``shift`` > 0 lets each query see
    that many future keys."""
    import jax.numpy as jnp

    D = q.shape[-1]
    s = jnp.einsum("shd,thd->hst", q[0].astype(jnp.float32) * D ** -0.5,
                   k[0].astype(jnp.float32), precision="highest")
    S, T = s.shape[1:]
    mask = jnp.arange(T)[None] <= jnp.arange(S)[:, None] + shift
    return jnp.where(mask[None], s, -jnp.inf)


def readings(seed: int) -> list:
    """``(name, tol, admitted, excess)`` for every sound kernel and
    control; ``admitted`` is None where the reading is not judged."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.decode_attention import flash_decode, flash_decode_paged
    from repro.kernels.flash_attention import flash_attention

    interpret = jax.default_backend() != "tpu"
    x = smoke.kernel_inputs(seed)
    q, kc, vc, lengths = x["q"], x["k_cache"], x["v_cache"], x["lengths"]
    qs, ks, vs = x["q_prefill"], x["k_prefill"], x["v_prefill"]
    B, H, D = q.shape
    want_d = smoke.oracle(ref.decode_attention, q, kc, vc, lengths)
    want_p = smoke.oracle(ref.attention, qs, ks, vs)

    s_d = decode_scores(q, kc, lengths)
    v_d = vc.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B * H, -1, D)

    def decode_online(**kw):
        return online_attention(s_d, v_d, smoke.PAGE, **kw).reshape(B, H, D)

    s_p = prefill_scores(qs, ks)
    v_p = vs[0].astype(jnp.float32).transpose(1, 0, 2)

    def prefill_online(s=s_p, **kw):
        return online_attention(s, v_p, 512, **kw).transpose(1, 0, 2)[None]

    dropped = jnp.maximum(lengths - 1, 1)
    dec, pre = smoke.DECODE_TOL, smoke.PREFILL_TOL
    cases = [
        ("decode kernel flash_decode_paged", dec, True, want_d,
         flash_decode_paged(q, x["k_pages"], x["v_pages"], x["layer"],
                            x["table"], lengths, interpret=interpret)),
        ("decode kernel flash_decode", dec, True, want_d,
         flash_decode(q, kc, vc, lengths, interpret=interpret)),
        ("decode online f32 (emulation check)", dec, True, want_d,
         decode_online()),
        ("decode matmul operands in bf16", dec, True, want_d,
         ref.decode_attention(q, kc, vc, lengths)),
        ("decode accumulator in bf16", dec, False, want_d,
         decode_online(acc_bf16=True)),
        ("decode softmax sum in bf16", dec, False, want_d,
         decode_online(l_bf16=True)),
        ("decode last position dropped", dec, False, want_d,
         smoke.oracle(ref.decode_attention, q, kc, vc, dropped)),
        ("prefill kernel flash_attention", pre, True, want_p,
         flash_attention(qs, ks, vs, causal=True, interpret=interpret)),
        ("prefill online f32 (emulation check)", pre, True, want_p,
         prefill_online()),
        ("prefill probabilities in bf16", pre, None, want_p,
         jnp.einsum("hst,thd->shd", bf16_round(jax.nn.softmax(s_p, -1)),
                    v_p.transpose(1, 0, 2), precision="highest")[None]),
        ("prefill accumulator in bf16", pre, False, want_p,
         prefill_online(acc_bf16=True)),
        ("prefill softmax sum in bf16", pre, False, want_p,
         prefill_online(l_bf16=True)),
        ("prefill one future key", pre, False, want_p,
         prefill_online(s=prefill_scores(qs, ks, shift=1))),
    ]
    return [(name, tol, admitted,
             smoke.kernel_error(jnp.asarray(got).astype(jnp.bfloat16),
                                want)[2])
            for name, tol, admitted, want, got in cases]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    bad = []
    for name, tol, admitted, excess in readings(args.seed):
        verdict = "admitted" if excess <= tol else "rejected"
        print(f"{name}: error beyond bf16 rounding / max|oracle| {excess}"
              f" -> {verdict} at {tol}"
              + (" (not judged)" if admitted is None else ""))
        if admitted is not None and admitted != (verdict == "admitted"):
            bad.append(name)
    if bad:
        raise SystemExit(f"the kernel limits misjudge: {bad}")
    print("the kernel limits separate every judged control")


if __name__ == "__main__":
    main()
