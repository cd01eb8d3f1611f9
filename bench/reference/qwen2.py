"""Plain float32 reference of a Qwen2-type dense decoder (Qwen1.5).

Written from the published description (the ``Qwen2ForCausalLM`` block)
and independent of the program: no kernel, no cache, no batching. Each
layer is

    h = x + o_proj(attn(rope(q_proj(n1)), rope(k_proj(n1)), v_proj(n1)))
    y = h + down_proj(silu(gate_proj(n2)) * up_proj(n2))

with ``n1 = rmsnorm(x) * ln1``, ``n2 = rmsnorm(h) * ln2``, biases on q, k
and v, causal softmax attention scaled by ``hd ** -0.5``, rotary
embedding on the two halves of each head (``rotate_half``) with
frequencies ``theta ** (-2i / hd)``, and logits ``rmsnorm(x_L) *
final_norm`` times the tied embedding or the head. Everything is float32
at ``default_matmul_precision("highest")``. The weights are the bf16
arrays of ``bench/families/qwen2.py``, upcast one layer at a time inside
the scan, so a model whose float32 weights would not fit still runs.

``quant="fp8"`` is the control: every matrix product takes both of its
operands rounded to float8 (e4m3) with a per-tensor scale for weights
and a per-row scale for activations, the step below the configuration's
bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fp8(x, axis=None):
    """``x`` rounded to e4m3 under an amax scale (per tensor or per row)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(quant, spec, a, b, a_row_axis=-1, b_tensor=True):
    if quant:
        a = _fp8(a, axis=a_row_axis)
        b = _fp8(b) if b_tensor else _fp8(b, axis=-1)
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, hd] at positions 0..T-1."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]   # [T, hd/2]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _layer(x, w, c, quant):
    T = x.shape[0]
    H, K, hd, eps = c["H"], c["K"], c["hd"], c["eps"]
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    n1 = _rmsnorm(x, w["ln1"], eps)
    q = (_mm(quant, "td,df->tf", n1, w["wq"]) + w["bq"]).reshape(T, H, hd)
    k = (_mm(quant, "td,df->tf", n1, w["wk"]) + w["bk"]).reshape(T, K, hd)
    v = (_mm(quant, "td,df->tf", n1, w["wv"]) + w["bv"]).reshape(T, K, hd)
    q, k = _rope(q, c["theta"]), _rope(k, c["theta"])
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    s = _mm(quant, "qhd,khd->hqk", q, k, b_tensor=False) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm(quant, "hqk,khd->qhd", p, v, b_tensor=False)
    h = x + _mm(quant, "tf,fd->td", o.reshape(T, H * hd), w["wo"])
    n2 = _rmsnorm(h, w["ln2"], eps)
    g = _mm(quant, "td,df->tf", n2, w["w_gate"])
    u = _mm(quant, "td,df->tf", n2, w["w_up"])
    return h + _mm(quant, "tf,fd->td", jax.nn.silu(g) * u, w["w_down"])


LAYER_KEYS = ("ln1", "ln2", "wq", "bq", "wk", "bk", "wv", "bv", "wo",
              "w_gate", "w_up", "w_down")


def _logits(w, c, tokens, quant):
    x = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in LAYER_KEYS}
    x, _ = jax.lax.scan(lambda h, lw: (_layer(h, lw, c, quant), None),
                        x, layers)
    x = _rmsnorm(x, w["final_norm"].astype(jnp.float32), c["eps"])
    head = (w["embed"].T if "lm_head" not in w else w["lm_head"])
    return _mm(quant, "td,dv->tv", x, head.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("c",))
def _gaps(w, tokens, targets, c):
    with jax.default_matmul_precision("highest"):
        logits = _logits(w, dict(c), tokens, False)
    rows = jnp.arange(logits.shape[0])[None]
    return logits.max(-1)[None] - logits[rows, targets]


@functools.partial(jax.jit, static_argnames=("c",))
def _fp8_argmax(w, tokens, c):
    with jax.default_matmul_precision("highest"):
        logits = _logits(w, dict(c), tokens, True)
    return jnp.argmax(logits, -1).astype(jnp.int32)


def consts(cfg: dict) -> tuple:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return tuple(sorted(dict(
        H=H, K=cfg["num_key_value_heads"], hd=cfg.get("head_dim", D // H),
        eps=float(cfg["rms_norm_eps"]),
        theta=float(cfg["rope_theta"])).items()))


def gaps(w: dict, cfg: dict, tokens, targets):
    """Per position, how far below the reference's largest logit the
    logit of each row of ``targets`` lies: ``[k, T]`` from ``tokens`` [T]
    and ``targets`` [k, T] (row r, position t: a token that follows
    ``tokens[:t + 1]``)."""
    return _gaps(w, tokens, targets, c=consts(cfg))


def control_argmax(w: dict, cfg: dict, tokens):
    """The fp8 control's first choice at each position of ``tokens``."""
    return _fp8_argmax(w, tokens, c=consts(cfg))
