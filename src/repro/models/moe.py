"""Mixture-of-Experts layer (phi3.5-moe: 16e top-2; kimi-k2: 384e top-8).

Two interchangeable implementations (cfg.moe_impl):

``dense``  — GShard-style capacity-factor dispatch with one-hot einsums.
             pjit-friendly (XLA SPMD partitions the expert dimension over
             the "model" axis = expert parallelism), numerically the
             paper-era baseline. Cost: the dispatch/combine einsums carry
             O(tokens · E·C · D) FLOPs — visible in the roofline and
             attacked in the §Perf hillclimb.

``gather`` — sort-based dispatch + grouped GEMM via jax.lax.ragged_dot,
             FLOPs proportional to routed tokens only. Runs inside
             shard_map over the "model" axis: each shard computes its
             local experts' contributions for all tokens, then psums.

Both apply top-k routing with softmax-renormalized gates and optional
shared experts (kimi-k2) that every token visits. They serve training,
where a token over an expert's capacity may be dropped.

``apply_moe_held`` is the routed layer as served (``Model.prefill`` /
``decode`` and the paged entry points): it drops nothing, so a token's
output does not depend on how its prompt was chunked or on what the
other slots hold, and it computes only the part of the experts that
this chip holds (``ModelConfig.held_experts``), routing over all of
them. On one chip it runs without the exchange; the partial result goes
on to the next layer.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import dense_init

Params = Dict[str, Any]


def init_moe(key, cfg: ModelConfig) -> Params:
    """Router over all ``num_experts``; expert weights of the held ones."""
    D, F = cfg.d_model, cfg.moe_d_ff
    E = cfg.held_experts[1]
    dt = cfg.p_dtype()
    ks = jax.random.split(key, 5)
    std = D ** -0.5
    p = {
        "router": dense_init(ks[0], D, cfg.num_experts, jnp.float32),
        "wi": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * std).astype(dt),
        "wg": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * std).astype(dt),
        "wo": (jax.random.normal(ks[3], (E, F, D), jnp.float32) * F ** -0.5).astype(dt),
    }
    if cfg.router_score == "sigmoid":
        p["bias"] = jnp.zeros((cfg.num_experts,), jnp.float32)
    if cfg.num_shared_experts:
        Fs = F * cfg.num_shared_experts
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": dense_init(kss[0], D, Fs, dt),
            "wg": dense_init(kss[1], D, Fs, dt),
            "wo": dense_init(kss[2], Fs, D, dt, scale=Fs ** -0.5),
        }
    return p


def route(p: Params, cfg: ModelConfig, x: jax.Array):
    """Top-k routing over all ``num_experts``, in float32.

    x: [..., D] -> (gates [..., k], idx [..., k], probs [..., E]). With
    ``router_score == "sigmoid"`` (deepseek-v3, ``noaux_tc`` with one
    group) the scores are ``sigmoid(logits)`` and the choice is made on
    ``scores + bias``; the gates are the chosen scores without the bias.
    Either way the gates are normalised over the chosen k and scaled by
    ``routed_scale``.
    """
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    k = cfg.experts_per_token
    if cfg.router_score == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(probs + p["bias"].astype(jnp.float32), k)
        gates = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates * cfg.routed_scale, idx, probs


def _route(p: Params, cfg: ModelConfig, x: jax.Array):
    """Top-k routing. x: [..., D] -> gates [..., k], idx [..., k], aux."""
    gates, idx, probs = route(p, cfg, x)
    # load-balancing aux loss (Switch-style)
    E = cfg.num_experts
    me = probs.reshape(-1, E).mean(axis=0)                     # [E]
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(
        1.0 / idx.size)
    aux = E * jnp.sum(me * ce)
    return gates, idx, aux


def _expert_ffn(wi, wg, wo, x):
    """x: [..., D] through one expert's SwiGLU. Weights [..., D, F] etc."""
    h = jax.nn.silu(jnp.einsum("...td,...df->...tf", x, wg))
    h = h * jnp.einsum("...td,...df->...tf", x, wi)
    return jnp.einsum("...tf,...fd->...td", h, wo)


def apply_moe_dense(p: Params, cfg: ModelConfig, x: jax.Array):
    """GShard dispatch, grouped by batch row (the standard data-shard
    grouping so dispatch tensors stay O(S·E·C_group) per group).

    x: [B, S, D] -> ([B, S, D], aux_loss).
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    gates, idx, aux = _route(p, cfg, x)                        # [B, S, k]

    # per-group (per batch row) capacity
    C = max(1, int(cfg.capacity_factor * S * k / E))
    onehot_e = jax.nn.one_hot(idx, E, dtype=jnp.int32)         # [B, S, k, E]
    flat = onehot_e.reshape(B, S * k, E)
    pos = jnp.cumsum(flat, axis=1) - 1                         # arrival order
    pos_in_expert = (pos.reshape(B, S, k, E) * onehot_e).sum(-1)  # [B, S, k]
    keep = pos_in_expert < C                                   # drop overflow
    gates = gates * keep.astype(gates.dtype)

    # one-hot dispatch [B, S, k, E, C] -> summed over k: [B, S, E, C]
    oh_c = jax.nn.one_hot(jnp.where(keep, pos_in_expert, C), C + 1,
                          dtype=x.dtype)[..., :C]              # [B, S, k, C]
    oh_e = onehot_e.astype(x.dtype)
    disp = jnp.einsum("bske,bskc->bsec", oh_e, oh_c)
    comb = jnp.einsum("bske,bskc,bsk->bsec", oh_e, oh_c,
                      gates.astype(x.dtype))

    xe = jnp.einsum("bsd,bsec->becd", x, disp)                 # [B, E, C, D]
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["wg"]))
    h = h * jnp.einsum("becd,edf->becf", xe, p["wi"])
    ye = jnp.einsum("becf,efd->becd", h, p["wo"])              # [B, E, C, D]
    y = jnp.einsum("becd,bsec->bsd", ye, comb)                 # [B, S, D]

    if cfg.num_shared_experts:
        sh = p["shared"]
        y = y + _expert_ffn(sh["wi"], sh["wg"], sh["wo"], x)
    return y, aux


def apply_moe_gather(p: Params, cfg: ModelConfig, x: jax.Array,
                     axis_name: Optional[str] = None,
                     axis_size: int = 1):
    """Sort-based grouped-GEMM MoE (runs per model-shard under shard_map).

    When ``axis_name`` is given, ``p['wi']/['wg']/['wo']`` hold only the
    local expert slice [E_local, ...]; every shard routes its local
    tokens, processes the assignments that hit its local experts through
    a fixed-capacity ragged_dot buffer, and the caller psums the partial
    outputs over the axis. Compared to the GShard dense dispatch this
    moves **one activations-sized psum per layer** instead of
    [B,S,E,C]-sized dispatch products, and computes only routed tokens.
    """
    B, S, D = x.shape
    k = cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, D)
    gates, idx, aux = _route(p, cfg, xt)

    E_local = p["wi"].shape[0]
    if axis_name is not None:
        shard = jax.lax.axis_index(axis_name)
        lo = shard * E_local
    else:
        lo = 0

    flat_e = idx.reshape(-1) - lo                              # [T*k]
    flat_g = gates.reshape(-1)
    local = (flat_e >= 0) & (flat_e < E_local)
    flat_e = jnp.where(local, flat_e, E_local)                 # E_local = trash
    order = jnp.argsort(flat_e)                                # stable
    sorted_tok = order // k

    # fixed-capacity compute buffer: expected local assignments x slack
    expected = T * k / max(axis_size, 1)
    C_buf = int(min(T * k, max(1, cfg.capacity_factor * expected)))
    order_c = order[:C_buf]
    tok_c = sorted_tok[:C_buf]
    e_c = flat_e[order_c]
    # overflow beyond capacity is dropped (standard capacity behavior);
    # rows past sum(group_sizes) are zero-filled by ragged_dot
    group_sizes = jnp.bincount(e_c, length=E_local + 1)[:E_local]

    xs = xt[tok_c]                                             # [C_buf, D]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["wg"], group_sizes))
    h = h * jax.lax.ragged_dot(xs, p["wi"], group_sizes)
    ys = jax.lax.ragged_dot(h, p["wo"], group_sizes)           # [C_buf, D]
    keep = local[order_c]
    ys = ys * (flat_g[order_c] * keep.astype(flat_g.dtype)
               ).astype(ys.dtype)[:, None]
    yt = jnp.zeros((T, D), ys.dtype).at[tok_c].add(ys)

    if cfg.num_shared_experts and (axis_name is None):
        sh = p["shared"]
        yt = yt + _expert_ffn(sh["wi"], sh["wg"], sh["wo"], xt)
    return yt.reshape(B, S, D), aux


def apply_moe_held(p: Params, cfg: ModelConfig, x: jax.Array,
                   live: Optional[jax.Array] = None):
    """The routed layer as served: dropless, over the held experts.

    x: [B, S, D]; live: optional [B, S] bool, the tokens whose
    assignments are counted. Every token is routed over all
    ``num_experts``; the held experts ``[lo, lo + n)`` (``p["wi"]`` etc.
    hold exactly those) each run over all of the call's tokens, gated by
    the token's weight for that expert, zero where it chose another. So
    nothing is dropped and no token's output depends on another's. At
    decode the held experts' weights are read once a step whatever the
    batch, which is what sets the layer's time. Shared experts are added
    by every share. Returns ``(y, held)``: the share's part of the layer
    and the number of (token, held expert) assignments of the live
    tokens, an int32 scalar.
    """
    lo, n = cfg.held_experts
    gates, idx, _ = route(p, cfg, x)                           # [B, S, k]
    local = idx - lo
    onehot = jax.nn.one_hot(local, n, dtype=jnp.float32)       # 0 off-share
    comb = jnp.einsum("bsk,bske->bse", gates, onehot)          # [B, S, n]
    h = jax.nn.silu(jnp.einsum("bsd,edf->bsef", x, p["wg"]))
    h = h * jnp.einsum("bsd,edf->bsef", x, p["wi"])
    h = h * comb[..., None].astype(h.dtype)
    y = jnp.einsum("bsef,efd->bsd", h, p["wo"])
    if cfg.num_shared_experts:
        sh = p["shared"]
        y = y + _expert_ffn(sh["wi"], sh["wg"], sh["wo"], x)
    hits = (local >= 0) & (local < n)
    if live is not None:
        hits = hits & live[..., None]
    return y, hits.sum(dtype=jnp.int32)


def apply_moe(p: Params, cfg: ModelConfig, x: jax.Array):
    """Dispatch on cfg.moe_impl; 'gather' uses shard_map over the tensor
    axis when an activation-sharding policy is active (production mesh),
    or the single-shard fast path otherwise (CPU tests)."""
    if cfg.moe_impl != "gather":
        return apply_moe_dense(p, cfg, x)

    from ..sharding.ctx import current_rules
    rules = current_rules()
    if rules is None or rules.axis_size(rules.tensor_axis) == 1:
        return apply_moe_gather(p, cfg, x, axis_name=None, axis_size=1)

    from jax.sharding import PartitionSpec as P
    ta = rules.tensor_axis
    ba = rules.batch_axes
    tsize = rules.axis_size(ta)
    bspec = ba if x.shape[0] % rules.axis_size(ba) == 0 else None

    routed = {"router": p["router"], "wi": p["wi"], "wg": p["wg"],
              "wo": p["wo"]}

    all_axes = tuple(rules.mesh.axis_names)

    def local_moe(x_loc, router, wi, wg, wo):
        y, aux = apply_moe_gather(
            {"router": router, "wi": wi, "wg": wg, "wo": wo},
            cfg, x_loc, axis_name=ta, axis_size=tsize)
        return jax.lax.psum(y, ta), jax.lax.pmean(aux, all_axes)

    y, aux = jax.shard_map(
        local_moe, mesh=rules.mesh,
        in_specs=(P(bspec, None, None), P(None, None),
                  P(ta, None, None), P(ta, None, None), P(ta, None, None)),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, routed["router"], routed["wi"], routed["wg"], routed["wo"])

    if cfg.num_shared_experts:
        sh = p["shared"]
        y = y + _expert_ffn(sh["wi"], sh["wg"], sh["wo"], x)
    return y, aux
