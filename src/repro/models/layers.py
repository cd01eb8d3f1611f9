"""Shared transformer layers: norms, rotary, GQA attention, SwiGLU MLP.

Pure-pytree style: ``init_*`` builds a dict of arrays, ``apply_*`` consumes
it. Sharding is annotated at the training-step level (sharding/rules.py
maps parameter paths to PartitionSpecs), so layers stay mesh-agnostic.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops
from .config import ModelConfig

Params = Dict[str, Any]


def _norm_init(D: int, dtype) -> jax.Array:
    return jnp.ones((D,), dtype)


def dense_init(key, fan_in: int, fan_out: int, dtype,
               scale: Optional[float] = None) -> jax.Array:
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, (fan_in, fan_out), jnp.float32) * std).astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with a hand-written VJP.

    Autodiff through the f32 variance path materializes f32 [B,S,D]
    cotangents, and XLA then places the per-layer tensor-parallel
    all-reduces on the f32 merged gradient — 2x the bytes (measured at
    llama3/train_4k; EXPERIMENTS.md §Perf cell 2). The custom backward
    does all math in f32 internally but hands back cotangents in the
    activation dtype, keeping every cross-device gradient tensor narrow.

        y  = x * r * w,          r = rsqrt(mean(x^2) + eps)
        dx = r*(w*g) - x * r^3 * mean(x*w*g)
        dw = sum_batch(x * r * g)
    """
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * scale * w.astype(x.dtype)


def _rms_fwd(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)                     # [..., 1] f32
    y = x * r.astype(x.dtype) * w.astype(x.dtype)
    return y, (x, w, r)


def _rms_bwd(eps, res, g):
    x, w, r = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    xwg = jnp.mean(xf * wf * gf, axis=-1, keepdims=True)   # [..., 1]
    dx = r * wf * gf - xf * (r ** 3) * xwg
    dw = jnp.sum((xf * r * gf).reshape(-1, x.shape[-1]), axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: [..., S, H, D]; positions: [..., S].
    Angles are computed in f32; cos/sin are cast to the activation dtype
    before the rotation so large tensors (and their cotangents) stay
    narrow — see rms_norm."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, d_model: Optional[int] = None) -> Params:
    D = d_model or cfg.d_model
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    dt = cfg.p_dtype()
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * hd, dt),
        "wk": dense_init(ks[1], D, K * hd, dt),
        "wv": dense_init(ks[2], D, K * hd, dt),
        "wo": dense_init(ks[3], H * hd, D, dt, scale=(H * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((K * hd,), dt)
        p["bv"] = jnp.zeros((K * hd,), dt)
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x: jax.Array,
                 positions: jax.Array, use_rope: bool = True):
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q = jnp.einsum("bsd,df->bsf", x, p["wq"])
    k = jnp.einsum("bsd,df->bsf", x, p["wk"])
    v = jnp.einsum("bsd,df->bsf", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, K, hd)
    v = v.reshape(B, S, K, hd)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def apply_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                    positions: jax.Array, causal: bool = True) -> jax.Array:
    """Full-sequence (training / prefill) self-attention."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    o = ops.attention(q, k, v, causal=causal)
    B, S = x.shape[:2]
    return jnp.einsum("bsf,fd->bsd", o.reshape(B, S, -1), p["wo"])


def apply_attention_prefill(p: Params, cfg: ModelConfig, x: jax.Array,
                            positions: jax.Array,
                            ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Prefill: returns output and the (k, v) cache for this layer."""
    q, k, v = _project_qkv(p, cfg, x, positions)
    o = ops.attention(q, k, v, causal=True)
    B, S = x.shape[:2]
    out = jnp.einsum("bsf,fd->bsd", o.reshape(B, S, -1), p["wo"])
    return out, (k, v)


def apply_attention_decode(p: Params, cfg: ModelConfig, x: jax.Array,
                           cache_k: jax.Array, cache_v: jax.Array,
                           lengths: jax.Array,
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: [B, 1, D]; cache_[kv]: [B, S_max, K, hd];
    lengths: [B] valid entries (the new token is written at ``lengths``).

    Cache-update policy (cfg.decode_cache_update):
      * "onehot"  — per-row masked add; handles ragged lengths but reads
        AND rewrites the full cache every step (paper-era baseline).
      * "dynamic" — dynamic_update_slice at the (uniform) position; with
        the cache donated, XLA updates one slot in place. Requires
        synchronized decode (all rows share a position), which the
        serving engine guarantees.
    """
    B = x.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    q, k, v = _project_qkv(p, cfg, x, lengths[:, None], use_rope=True)
    if cfg.decode_cache_update == "dynamic":
        pos = lengths[0]
        cache_k = jax.lax.dynamic_update_slice(
            cache_k, k.astype(cache_k.dtype), (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(
            cache_v, v.astype(cache_v.dtype), (0, pos, 0, 0))
    else:
        idx = lengths  # [B]
        oh = jax.nn.one_hot(idx, cache_k.shape[1], dtype=cache_k.dtype)
        cache_k = cache_k + oh[:, :, None, None] * k.astype(cache_k.dtype)
        cache_v = cache_v + oh[:, :, None, None] * v.astype(cache_v.dtype)
    o = ops.decode_attention(q[:, 0], cache_k, cache_v, lengths + 1)
    out = jnp.einsum("bf,fd->bd", o.reshape(B, -1), p["wo"])[:, None, :]
    return out, cache_k, cache_v


def paged_decode_rows(page_table: jax.Array, lengths: jax.Array,
                      slot_mask: jax.Array, page: int):
    """Where one decode step writes and what it attends, the same in every
    layer: ``(pid, off, att_len)``, each [B]. The new token lands at row
    ``off`` of page ``pid`` (a masked slot's at the null page 0) and
    ``att_len`` positions are attended (0 for a masked slot)."""
    B = lengths.shape[0]
    pid = jnp.where(slot_mask, page_table[jnp.arange(B), lengths // page], 0)
    return pid, lengths % page, jnp.where(slot_mask, lengths + 1, 0)


def apply_attention_decode_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                                 k_pages: jax.Array, v_pages: jax.Array,
                                 layer, page_table: jax.Array,
                                 lengths: jax.Array, rows,
                                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode of layer ``layer`` against the shared page slab.

    x: [B, 1, D]; k_pages/v_pages: [L, P, page, K * hd] — the WHOLE slab,
    shared by every sequence, page 0 reserved as the null page; layer:
    int32 scalar; page_table: [B, M] per-slot page ids; lengths: [B]
    valid cache entries (the new token is written at position
    ``lengths``); rows: ``paged_decode_rows`` of the step, made once
    outside the layer loop. Idle serving slots (False in its
    ``slot_mask``) write to the null page and attend nothing, so a dead
    slot can neither corrupt a live sequence's pages nor read stale
    ones. The write is one scatter into the slab as passed in, so a
    donated slab carried through the layer loop is updated in place.

    Equivalent to ``apply_attention_decode`` with the "onehot" policy on
    the gathered contiguous cache — per-slot ragged lengths (and thus
    ragged rope positions) are the normal case here, not an edge case.
    """
    B = x.shape[0]
    pid, off, att_len = rows
    q, k, v = _project_qkv(p, cfg, x, lengths[:, None], use_rope=True)
    k_pages = k_pages.at[layer, pid, off].set(
        k.reshape(B, -1).astype(k_pages.dtype))
    v_pages = v_pages.at[layer, pid, off].set(
        v.reshape(B, -1).astype(v_pages.dtype))
    o = ops.paged_decode_attention(q[:, 0], k_pages, v_pages, layer,
                                   page_table, att_len)
    out = jnp.einsum("bf,fd->bd", o.reshape(B, -1), p["wo"])[:, None, :]
    return out, k_pages, v_pages


def apply_attention_prefill_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                                  k_pages: jax.Array, v_pages: jax.Array,
                                  layer, page_table: jax.Array,
                                  start: jax.Array, n_valid: jax.Array,
                                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked prefill attention of layer ``layer`` for ONE request
    writing into the slab.

    x: [1, C, D] — the next chunk of the prompt, padded to the static
    chunk length C; k_pages/v_pages: [L, P, page, K * hd], the whole
    slab; page_table: [M] (this request's row); start: tokens already
    cached by earlier chunks; n_valid: real tokens in this chunk (the
    tail past it is padding: its K/V writes are redirected to the null
    page and no valid query row can attend that far right).

    The chunk's K/V are scattered into the pages FIRST, then
    ``ops.paged_prefill_attention`` attends the request's positions
    causally with the shifted mask ``col <= start + row`` — exactly
    ``ops.attention``'s semantics continued from a cache, f32 softmax and
    all, so chunked prefill matches one-shot prefill. On a TPU it reads
    only the blocks below ``start + n_valid``.
    """
    _, C, _ = x.shape
    page = k_pages.shape[2]
    tpos = start + jnp.arange(C, dtype=jnp.int32)              # [C]
    q, k, v = _project_qkv(p, cfg, x, tpos[None], use_rope=True)
    valid = jnp.arange(C) < n_valid
    pid = jnp.where(valid, page_table[tpos // page], 0)
    off = tpos % page
    k_pages = k_pages.at[layer, pid, off].set(
        k[0].reshape(C, -1).astype(k_pages.dtype))
    v_pages = v_pages.at[layer, pid, off].set(
        v[0].reshape(C, -1).astype(v_pages.dtype))
    o = ops.paged_prefill_attention(q[0], k_pages, v_pages, layer,
                                    page_table, start, n_valid)
    out = jnp.einsum("bsf,fd->bsd", o.reshape(1, C, -1), p["wo"])
    return out, k_pages, v_pages


# ---------------------------------------------------------------------------
# Latent attention (MLA, deepseek-v3)
# ---------------------------------------------------------------------------
#
# Per token, ``wq`` gives each head ``q_nope`` (qk_nope_head_dim) and
# ``q_pe`` (qk_rope_head_dim); ``wkv_a`` gives one latent ``c``
# (kv_lora_rank, then ``kv_norm``) and one rope key ``k_pe`` shared by
# all heads; ``wkv_b`` would expand ``c`` into each head's ``k_nope`` and
# value. Scores are ``(q_nope . k_nope + q_pe . k_pe) * (nope + rope) **
# -0.5``. Rope acts on ``q_pe`` and ``k_pe`` after DeepSeek's pair
# interleave (even lanes first, then odd), then rotates halves.
#
# Served in the absorbed form: ``W_UK`` (the key half of ``wkv_b``) goes
# into the query, ``q_lat = q_nope @ W_UK^T``, so a head's query is
# ``[q_lat, q_pe]`` against the cached row ``[c, k_pe]`` (576 lanes at
# Moonlight's widths); the value is ``c`` itself, and ``W_UV`` (the value
# half) takes the head's output out of the latent. The cache holds that
# one row per token, and prefill and decode read it alike. The slab's
# rows are zero-padded to whole 128-lane tiles (640 lanes for 576): XLA
# stores a 576-lane minor dim in 640 anyway, and a kernel's copy of a row
# can only take whole tiles; the queries get the same zero lanes.


def init_mla(key, cfg: ModelConfig) -> Params:
    D, H = cfg.d_model, cfg.num_heads
    R, rp = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    dt = cfg.p_dtype()
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], D, H * (nope + rp), dt),
        "wkv_a": dense_init(ks[1], D, R + rp, dt),
        "kv_norm": _norm_init(R, dt),
        "wkv_b": dense_init(ks[2], R, H * (nope + vd), dt),
        "wo": dense_init(ks[3], H * vd, D, dt, scale=(H * vd) ** -0.5),
    }


def _interleaved_rope(x: jax.Array, positions: jax.Array,
                      theta: float) -> jax.Array:
    """DeepSeek's rope: lanes ``(x0, x1, x2, ...)`` regrouped as ``(x0,
    x2, ..., x1, x3, ...)``, then the half rotation of :func:`rope`.
    x: [..., S, H, D]; positions: [..., S]."""
    D = x.shape[-1]
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], D // 2, 2), -1, -2)
    return rope(x.reshape(*x.shape[:-2], D), positions, theta)


def latent_lanes(cfg: ModelConfig) -> int:
    """Lanes of a latent slab row: the latent and the rope key, rounded
    up to whole 128-lane tiles."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _pad_lanes(x: jax.Array, n: int) -> jax.Array:
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, n - x.shape[-1])])


def mla_scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _mla_wkv_b(p: Params, cfg: ModelConfig):
    """``(W_UK [R, H, nope], W_UV [R, H, v])``, the halves of wkv_b."""
    H, nope = cfg.num_heads, cfg.qk_nope_head_dim
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, H, -1)
    return w[..., :nope], w[..., nope:]


def mla_project(p: Params, cfg: ModelConfig, x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, D] -> (queries [B, S, H, R + rope], rows [B, S, R +
    rope]): the absorbed queries and the latent rows to cache."""
    B, S, _ = x.shape
    H, R = cfg.num_heads, cfg.kv_lora_rank
    nope = cfg.qk_nope_head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(B, S, H, -1)
    q_pe = _interleaved_rope(q[..., nope:], positions, cfg.rope_theta)
    w_uk, _ = _mla_wkv_b(p, cfg)
    q_lat = jnp.einsum("bshn,rhn->bshr", q[..., :nope], w_uk)
    kv = jnp.einsum("bsd,df->bsf", x, p["wkv_a"])
    c = rms_norm(kv[..., :R], p["kv_norm"], cfg.norm_eps)
    k_pe = _interleaved_rope(kv[..., None, R:], positions,
                             cfg.rope_theta)[..., 0, :]
    return (jnp.concatenate([q_lat, q_pe], axis=-1),
            jnp.concatenate([c, k_pe], axis=-1))


def mla_out(p: Params, cfg: ModelConfig, o_lat: jax.Array) -> jax.Array:
    """Heads' outputs in the latent, [B, S, H, R] -> [B, S, D]."""
    B, S = o_lat.shape[:2]
    _, w_uv = _mla_wkv_b(p, cfg)
    o = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv)
    return jnp.einsum("bsf,fd->bsd", o.reshape(B, S, -1), p["wo"])


def latent_attention(q: jax.Array, rows: jax.Array, mask: jax.Array,
                     scale: float, v_dim: int) -> jax.Array:
    """Softmax attention of absorbed queries over latent rows, in f32.
    q: [B, S, H, C]; rows: [B, T, C]; mask: [S, T] -> [B, S, H, v_dim]
    (the value of a row is its first ``v_dim`` lanes)."""
    rf = rows.astype(jnp.float32)
    s = jnp.einsum("bqhc,btc->bhqt", q.astype(jnp.float32), rf) * scale
    s = jnp.where(mask[None, None], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqt,btr->bqhr", probs, rf[..., :v_dim])
    return o.astype(q.dtype)


def apply_mla(p: Params, cfg: ModelConfig, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    """Full-sequence causal latent attention (training, reference tests)."""
    S = x.shape[1]
    q, rows = mla_project(p, cfg, x, positions)
    causal = jnp.tril(jnp.ones((S, S), bool))
    o = latent_attention(q, rows, causal, mla_scale(cfg), cfg.kv_lora_rank)
    return mla_out(p, cfg, o)


def apply_mla_decode_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                           kv_pages: jax.Array, layer,
                           page_table: jax.Array, lengths: jax.Array, rows
                           ) -> Tuple[jax.Array, jax.Array]:
    """One-token latent decode of layer ``layer`` against the latent slab.

    x: [B, 1, D]; kv_pages: [L, P, page, lanes], the whole slab, page
    0 the null page; the rest as in :func:`apply_attention_decode_paged`
    (``rows`` is ``paged_decode_rows`` of the step): the new row is
    written at ``lengths`` (a masked slot's to the null page) by one
    scatter into the slab as passed in, then
    ``ops.latent_decode_attention`` reads each slot's live pages once,
    as keys (all lanes) and values (the latent's), and ``W_UV`` and
    ``wo`` take the heads' outputs out of the latent."""
    pid, off, att_len = rows
    lanes = kv_pages.shape[3]
    q, row = mla_project(p, cfg, x, lengths[:, None])
    q, row = _pad_lanes(q, lanes), _pad_lanes(row, lanes)
    kv_pages = kv_pages.at[layer, pid, off].set(
        row[:, 0].astype(kv_pages.dtype))
    o = ops.latent_decode_attention(q[:, 0], kv_pages, layer, page_table,
                                    att_len, sm_scale=mla_scale(cfg),
                                    v_dim=cfg.kv_lora_rank)
    return mla_out(p, cfg, o[:, None]), kv_pages


def apply_mla_prefill_paged(p: Params, cfg: ModelConfig, x: jax.Array,
                            kv_pages: jax.Array, layer,
                            page_table: jax.Array, start: jax.Array,
                            n_valid: jax.Array
                            ) -> Tuple[jax.Array, jax.Array]:
    """Chunked latent prefill of layer ``layer`` for ONE request, as
    :func:`apply_attention_prefill_paged` does it: the chunk's rows are
    scattered into the slab first (padding rows to the null page), then
    ``ops.latent_prefill_attention`` attends the request's positions
    with its absorbed queries under ``col <= start + row``, f32
    softmax."""
    _, C, _ = x.shape
    page, lanes = kv_pages.shape[2:]
    tpos = start + jnp.arange(C, dtype=jnp.int32)
    q, rows = mla_project(p, cfg, x, tpos[None])
    q, rows = _pad_lanes(q, lanes), _pad_lanes(rows, lanes)
    pid = jnp.where(jnp.arange(C) < n_valid, page_table[tpos // page], 0)
    kv_pages = kv_pages.at[layer, pid, tpos % page].set(
        rows[0].astype(kv_pages.dtype))
    o = ops.latent_prefill_attention(q[0], kv_pages, layer, page_table,
                                     start, n_valid, sm_scale=mla_scale(cfg),
                                     v_dim=cfg.kv_lora_rank)
    return mla_out(p, cfg, o[None]), kv_pages


def init_cross_attention(key, cfg: ModelConfig) -> Params:
    return init_attention(key, cfg)


def apply_cross_attention(p: Params, cfg: ModelConfig, x: jax.Array,
                          enc_kv: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """Decoder cross-attention. enc_kv = (k, v) precomputed from encoder
    output: [B, T, K, hd]."""
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.hd
    q = jnp.einsum("bsd,df->bsf", x, p["wq"]).reshape(B, S, H, hd)
    k, v = enc_kv
    o = ops.attention(q, k, v, causal=False)
    return jnp.einsum("bsf,fd->bsd", o.reshape(B, S, -1), p["wo"])


def encoder_kv(p: Params, cfg: ModelConfig, enc_out: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    B, T, _ = enc_out.shape
    K, hd = cfg.num_kv_heads, cfg.hd
    k = jnp.einsum("btd,df->btf", enc_out, p["wk"]).reshape(B, T, K, hd)
    v = jnp.einsum("btd,df->btf", enc_out, p["wv"]).reshape(B, T, K, hd)
    return k, v


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    dt = cfg.p_dtype()
    ks = jax.random.split(key, 3)
    return {
        "wi": dense_init(ks[0], D, F, dt),
        "wg": dense_init(ks[1], D, F, dt),
        "wo": dense_init(ks[2], F, D, dt, scale=F ** -0.5),
    }


def apply_mlp(p: Params, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wg"]))
    h = h * jnp.einsum("bsd,df->bsf", x, p["wi"])
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embedding(key, cfg: ModelConfig) -> Params:
    dt = cfg.p_dtype()
    ks = jax.random.split(key, 2)
    p = {"tok": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                   jnp.float32) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)
    return p


def embed(p: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = jnp.take(p["tok"], tokens, axis=0).astype(cfg.act_dtype())
    return x * cfg.emb_scale


def unembed(p: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["tok"].astype(x.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["head"].astype(x.dtype))
    if cfg.logit_soft_cap is not None:
        logits = cfg.logit_soft_cap * jnp.tanh(logits / cfg.logit_soft_cap)
    return logits


# ---------------------------------------------------------------------------
# Dense transformer block
# ---------------------------------------------------------------------------


def init_dense_block(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    return {
        "attn": init_attention(ks[0], cfg),
        "mlp": init_mlp(ks[1], cfg),
        "norm1": _norm_init(cfg.d_model, cfg.p_dtype()),
        "norm2": _norm_init(cfg.d_model, cfg.p_dtype()),
    }


def apply_dense_block(p: Params, cfg: ModelConfig, x: jax.Array,
                      positions: jax.Array) -> jax.Array:
    # Sub-block boundaries are pinned too: left free, XLA's partitioner
    # shards the f32 rms intermediates over the tensor axis and pays
    # full-width f32 all-reduces in the backward (measured: +2x collective
    # bytes at llama3/train_4k — EXPERIMENTS.md §Perf cell 2 iter 3).
    from ..sharding.ctx import constrain
    r = cfg.residual_scale
    h = constrain(rms_norm(x, p["norm1"], cfg.norm_eps), "batch", "seq", None)
    x = x + r * constrain(apply_attention(p["attn"], cfg, h, positions),
                          "batch", "seq", None)
    h = constrain(rms_norm(x, p["norm2"], cfg.norm_eps), "batch", "seq", None)
    x = x + r * constrain(apply_mlp(p["mlp"], h), "batch", "seq", None)
    return x


def apply_dense_block_prefill(p, cfg, x, positions):
    r = cfg.residual_scale
    a, kv = apply_attention_prefill(p["attn"], cfg,
                                    rms_norm(x, p["norm1"], cfg.norm_eps),
                                    positions)
    x = x + r * a
    x = x + r * apply_mlp(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps))
    return x, kv


def apply_dense_block_decode(p, cfg, x, cache_k, cache_v, lengths):
    r = cfg.residual_scale
    a, ck, cv = apply_attention_decode(
        p["attn"], cfg, rms_norm(x, p["norm1"], cfg.norm_eps),
        cache_k, cache_v, lengths)
    x = x + r * a
    x = x + r * apply_mlp(p["mlp"], rms_norm(x, p["norm2"], cfg.norm_eps))
    return x, ck, cv
