"""Plain float32 reference of a DeepSeek-V3-type decoder (Moonlight).

Written from the published description (``DeepseekV3ForCausalLM``) and
independent of the program: no kernel, no cache, no batching, and latent
attention in its expanded form, the keys and values made from the latent
as the published block makes them. Each layer is

    h = x + o_proj(attn(n1))        n1 = rmsnorm(x) * ln1
    y = h + ffn(n2)                 n2 = rmsnorm(h) * ln2

Attention: ``q = q_proj(n1)`` split per head into ``q_nope`` (128) and
``q_pe`` (64); ``kv_a_proj_with_mqa(n1)`` split into the latent ``c``
(512) and one ``k_pe`` (64) shared by the heads; ``kv_b_proj(
rmsnorm(c) * kv_a_layernorm)`` split per head into ``k_nope`` (128) and
the value (128). Rope acts on ``q_pe`` and ``k_pe`` after the pair
interleave of ``apply_rotary_pos_emb`` (view ``[d/2, 2]``, transpose),
then ``x * cos + rotate_half(x) * sin`` with frequencies ``theta **
(-2i / 64)``. Scores ``[q_nope, q_pe] . [k_nope, k_pe] * 192 ** -0.5``,
causal softmax; the heads' values through ``o_proj``.

FFN: the leading ``first_k_dense_replace`` layers a SwiGLU of width
``intermediate_size``; the others MoE: ``scores = sigmoid(n2 @ gate)``;
the top ``num_experts_per_tok`` of ``scores + e_score_correction_bias``
are chosen (``noaux_tc`` with one group); their scores, without the
bias, normalised over the chosen and times ``routed_scaling_factor``,
weigh the experts' SwiGLUs (width ``moe_intermediate_size``); the shared
experts (one SwiGLU of ``n_shared_experts`` times that width) are added.
This chip's share: only the held experts (``held_experts``) are
computed, densely over every token, each masked by the token's weight
for it, as the program's share is; routing is over all
``router_experts``.

Everything is float32 at ``default_matmul_precision("highest")``;
attention runs in blocks of query rows and the head in blocks of
positions, so that a 7,168-position teacher-forced pass fits beside the
weights. The weights are the bf16 arrays of
``bench/families/deepseek_v3.py``, upcast one layer at a time.
``quant="fp8"`` is the control: every matrix product takes both of its
operands rounded to float8 (e4m3), per-tensor scales for weights and
per-row for activations, the step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
ATTN_KEYS = ("ln1", "ln2", "wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE_KEYS = ATTN_KEYS + ("w_gate", "w_up", "w_down")
MOE_KEYS = ATTN_KEYS + ("router", "bias", "e_gate", "e_up", "e_down",
                        "s_gate", "s_up", "s_down")


def _fp8(x, axis=None):
    """``x`` rounded to e4m3 under an amax scale (per tensor or per row)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(quant, spec, a, b, b_tensor=True):
    if quant:
        a = _fp8(a, axis=-1)
        b = _fp8(b) if b_tensor else _fp8(b, axis=-1)
    return jnp.einsum(spec, a, b)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [T, heads, d] at positions 0..T-1, DeepSeek's pair interleave
    first."""
    T, n, d = x.shape
    x = jnp.swapaxes(x.reshape(T, n, d // 2, 2), -1, -2).reshape(T, n, d)
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def _block(T: int, want: int = 512) -> int:
    return next(b for b in (want, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                if T % b == 0)


def _attention(x, w, c, quant):
    T = x.shape[0]
    H, R, nope, rp, vd = c["H"], c["R"], c["nope"], c["rope"], c["vd"]
    q = _mm(quant, "td,df->tf", x, w["wq"]).reshape(T, H, nope + rp)
    kv = _mm(quant, "td,df->tf", x, w["wkv_a"])
    lat = _rmsnorm(kv[:, :R], w["kv_norm"], c["eps"])
    kvb = _mm(quant, "tr,rf->tf", lat, w["wkv_b"]).reshape(T, H, nope + vd)
    k_pe = jnp.broadcast_to(_rope(kv[:, None, R:], c["theta"]), (T, H, rp))
    qh = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], c["theta"])],
                         -1)
    kh = jnp.concatenate([kvb[..., :nope], k_pe], -1)
    v = kvb[..., nope:]
    bq = _block(T)
    cols = jnp.arange(T)

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(qh, i * bq, bq)
        s = _mm(quant, "qhd,khd->hqk", qb, kh, b_tensor=False)
        s = s * (nope + rp) ** -0.5
        causal = cols[None] <= (i * bq + jnp.arange(bq))[:, None]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return _mm(quant, "hqk,khd->qhd", p, v, b_tensor=False)

    o = jax.lax.map(rows, jnp.arange(T // bq)).reshape(T, H * vd)
    return _mm(quant, "tf,fd->td", o, w["wo"])


def _swiglu(quant, x, g, u, d):
    return _mm(quant, "tf,fd->td", jax.nn.silu(
        _mm(quant, "td,df->tf", x, g)) * _mm(quant, "td,df->tf", x, u), d)


def _moe(x, w, c, quant):
    scores = jax.nn.sigmoid(_mm(quant, "td,de->te", x, w["router"]))
    _, idx = jax.lax.top_k(scores + w["bias"], c["k"])
    g = jnp.take_along_axis(scores, idx, -1)
    g = g / g.sum(-1, keepdims=True) * c["scale"]
    held = c["first_held"] + jnp.arange(w["e_gate"].shape[0])
    gate = jnp.sum(jnp.where(idx[..., None] == held, g[..., None], 0.0), 1)
    h = jax.nn.silu(_mm(quant, "td,edf->etf", x, w["e_gate"])) * _mm(
        quant, "td,edf->etf", x, w["e_up"])
    y = jnp.einsum("etd,te->td", _mm(quant, "etf,efd->etd", h, w["e_down"]),
                   gate)
    return y + _swiglu(quant, x, w["s_gate"], w["s_up"], w["s_down"])


def _layer(x, w, c, quant, ffn):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = x + _attention(_rmsnorm(x, w["ln1"], c["eps"]), w, c, quant)
    n2 = _rmsnorm(h, w["ln2"], c["eps"])
    if ffn == "dense":
        return h + _swiglu(quant, n2, w["w_gate"], w["w_up"], w["w_down"])
    return h + _moe(n2, w, c, quant)


def _hidden(w, c, tokens, quant):
    x = w["embed"][tokens].astype(jnp.float32)
    dense = {k: w["d_" + k] for k in DENSE_KEYS}
    x, _ = jax.lax.scan(lambda h, lw: (_layer(h, lw, c, quant, "dense"),
                                       None), x, dense)
    moe = {k: w[k] for k in MOE_KEYS}
    x, _ = jax.lax.scan(lambda h, lw: (_layer(h, lw, c, quant, "moe"),
                                       None), x, moe)
    return _rmsnorm(x, w["final_norm"].astype(jnp.float32), c["eps"])


def _logit_blocks(w, x, quant, fn):
    """``fn(logits_block, i, rows)`` over blocks of positions, stacked."""
    T = x.shape[0]
    bq = _block(T)
    head = w["lm_head"].astype(jnp.float32)

    def one(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * bq, bq)
        return fn(_mm(quant, "td,dv->tv", xb, head), i, bq)

    return jax.lax.map(one, jnp.arange(T // bq))


@functools.partial(jax.jit, static_argnames=("c",))
def _gaps(w, tokens, targets, c):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        x = _hidden(w, c, tokens, False)

        def gap(lg, i, bq):
            tb = jax.lax.dynamic_slice_in_dim(targets, i * bq, bq, axis=1)
            got = jnp.take_along_axis(lg[None], tb[..., None], -1)[..., 0]
            return lg.max(-1)[None] - got                      # [k, bq]

        g = _logit_blocks(w, x, False, gap)                    # [n, k, bq]
    return jnp.swapaxes(g, 0, 1).reshape(targets.shape)


@functools.partial(jax.jit, static_argnames=("c",))
def _fp8_argmax(w, tokens, c):
    c = dict(c)
    with jax.default_matmul_precision("highest"):
        x = _hidden(w, c, tokens, True)
        best = _logit_blocks(
            w, x, True, lambda lg, i, bq: jnp.argmax(lg, -1))
    return best.reshape(-1).astype(jnp.int32)


def consts(cfg: dict) -> tuple:
    return tuple(sorted(dict(
        H=cfg["num_attention_heads"], R=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vd=cfg["v_head_dim"], k=cfg["num_experts_per_tok"],
        scale=float(cfg["routed_scaling_factor"]),
        first_held=cfg["held_experts"]["first"],
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


def logits(w: dict, cfg: dict, tokens):
    """The reference's logits ``[T, V]`` (tests: small sizes only)."""
    c = dict(consts(cfg))
    with jax.default_matmul_precision("highest"):
        x = _hidden(w, c, tokens, False)
        return (x @ w["lm_head"].astype(jnp.float32))
