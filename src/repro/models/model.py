"""Model assembly for all 10 assigned architectures.

One ``Model`` class; family-specific blocks (dense / moe / rwkv / mamba
hybrid / enc-dec) are composed by ``lax.scan`` over stacked per-layer
parameters — essential to keep HLO size (and CPU compile time) bounded at
kimi-k2 scale. Provides:

    init(key)                 -> params pytree
    loss(params, batch)       -> (scalar loss, metrics dict)   [train_step]
    prefill(params, batch, max_len) -> (logits, cache)
    decode(params, cache, tokens)   -> (logits, new cache)     [serve_step]

Cache layout is family-specific (KV cache / WKV state / SSD state) and is
documented next to each prefill implementation.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from ..kernels import ops
from ..sharding.ctx import constrain
from . import layers as L
from . import mamba as M
from . import moe as X
from . import rwkv as R

Params = Dict[str, Any]


def _positions(B: int, S: int, offset: int = 0) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None] + offset, (B, S))


def _stack_init(key, n: int, init_fn):
    """Initialize n layers and stack leaves along a leading axis. One
    vmapped layer, not n unrolled ones: the values are the same, and the
    jitted init of a 24-layer model compiles in seconds, not a minute."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def scan_over(cfg: ModelConfig, body, carry, xs, length: int = None):
    """lax.scan over stacked layers, or an unrolled python loop when
    cfg.scan_layers=False (dry-run *analysis* compiles use the unrolled
    form so XLA cost analysis sees every layer exactly once)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, carry, xs, length=length)
    n = length if length is not None else jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x_i = None if xs is None else jax.tree.map(lambda a, i=i: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def cross_entropy(logits: jax.Array, labels: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Mean CE over labels >= 0. Returns (loss, accuracy).

    Written gather-free: with a vocab-sharded logits tensor, ``argmax`` /
    ``take_along_axis`` over the sharded axis force XLA SPMD to all-gather
    the full [B, S, V] logits (measured: ~17 GB/device per microbatch at
    llama3 scale). The one-hot-masked reductions below keep every
    collective at [B, S] size.
    """
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    vocab = logits.shape[-1]
    m = jnp.max(logits, axis=-1)                              # [B, S]
    logz = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
              == safe[..., None])                             # fused compare
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)   # [B, S]
    nll = (logz - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    acc = (gold >= m - 1e-6).astype(jnp.float32) * mask       # argmax==label
    return nll.sum() / denom, acc.sum() / denom


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ------------------------------------------------------------------ init

    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_layers, k_extra, k_norm = jax.random.split(key, 4)
        params: Params = {"embed": L.init_embedding(k_emb, cfg),
                          "final_norm": jnp.ones((cfg.d_model,), cfg.p_dtype())}
        fam = cfg.family
        if fam in ("dense", "vlm"):
            params["layers"] = _stack_init(
                k_layers, cfg.num_layers, lambda k: L.init_dense_block(k, cfg))
        elif fam == "moe":
            init_attn = L.init_mla if cfg.is_mla else L.init_attention

            def init_block(k, ffn_key, init_ffn):
                k1, k2 = jax.random.split(k)
                return {"attn": init_attn(k1, cfg), ffn_key: init_ffn(k2),
                        "norm1": jnp.ones((cfg.d_model,), cfg.p_dtype()),
                        "norm2": jnp.ones((cfg.d_model,), cfg.p_dtype())}
            n_dense = cfg.first_dense_layers
            if n_dense:
                params["dense_layers"] = _stack_init(
                    k_extra, n_dense, lambda k: init_block(
                        k, "mlp", lambda k2: L.init_mlp(k2, cfg)))
            params["layers"] = _stack_init(
                k_layers, cfg.num_layers - n_dense, lambda k: init_block(
                    k, "moe", lambda k2: X.init_moe(k2, cfg)))
        elif fam == "ssm":
            params["layers"] = _stack_init(
                k_layers, cfg.num_layers, lambda k: R.init_rwkv_block(k, cfg))
        elif fam == "hybrid":
            params["layers"] = _stack_init(
                k_layers, cfg.num_layers, lambda k: M.init_mamba_block(k, cfg))
            params["shared_attn"] = L.init_dense_block(k_extra, cfg)
        elif fam == "encdec":
            def init_dec_block(k):
                k1, k2, k3 = jax.random.split(k, 3)
                return {"self_attn": L.init_attention(k1, cfg),
                        "cross_attn": L.init_cross_attention(k2, cfg),
                        "mlp": L.init_mlp(k3, cfg),
                        "norm1": jnp.ones((cfg.d_model,), cfg.p_dtype()),
                        "norm2": jnp.ones((cfg.d_model,), cfg.p_dtype()),
                        "norm3": jnp.ones((cfg.d_model,), cfg.p_dtype())}
            params["enc_layers"] = _stack_init(
                k_layers, cfg.encoder_layers, lambda k: L.init_dense_block(k, cfg))
            params["layers"] = _stack_init(k_extra, cfg.num_layers, init_dec_block)
            params["enc_norm"] = jnp.ones((cfg.d_model,), cfg.p_dtype())
        else:
            raise ValueError(f"unknown family {fam!r}")
        return params

    def abstract_params(self) -> Params:
        return jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

    # -------------------------------------------------------------- forward

    def _maybe_remat(self, fn):
        if self.cfg.remat == "full":
            return jax.checkpoint(fn)
        return fn

    def _backbone(self, params: Params, x: jax.Array, positions: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
        """Run the stacked layers. Returns (hidden, aux_loss)."""
        cfg = self.cfg
        fam = cfg.family

        if fam in ("dense", "vlm"):
            def body(h, layer):
                h = constrain(h, "batch", "seq", None)
                return L.apply_dense_block(layer, cfg, h, positions), None
            body = self._maybe_remat(body)
            x, _ = scan_over(cfg, body, x, params["layers"])
            return x, jnp.zeros((), jnp.float32)

        if cfg.is_mla:
            def attend(p, h, pages, i):
                return L.apply_mla(p, cfg, h, positions), pages
            x, _, _ = self._layers(params, x, {}, attend)
            return x, jnp.zeros((), jnp.float32)

        if fam == "moe":
            def body(carry, layer):
                h, aux = carry
                h = constrain(h, "batch", "seq", None)
                a = L.apply_attention(layer["attn"], cfg,
                                      L.rms_norm(h, layer["norm1"], cfg.norm_eps),
                                      positions)
                h = h + a
                mo, mx = X.apply_moe(layer["moe"], cfg,
                                           L.rms_norm(h, layer["norm2"], cfg.norm_eps))
                return (h + mo, aux + mx), None
            body = self._maybe_remat(body)
            (x, aux), _ = scan_over(cfg, body, (x, jnp.zeros((), jnp.float32)),
                                       params["layers"])
            return x, aux / cfg.num_layers

        if fam == "ssm":
            def body(h, layer):
                h = constrain(h, "batch", "seq", None)
                h, _ = R.apply_rwkv_block(layer, cfg, h)
                return h, None
            body = self._maybe_remat(body)
            x, _ = scan_over(cfg, body, x, params["layers"])
            return x, jnp.zeros((), jnp.float32)

        if fam == "hybrid":
            # groups of `attn_every` mamba layers followed by the SHARED
            # attention block (zamba2: one block's weights reused).
            every = cfg.attn_every or cfg.num_layers
            n_groups = cfg.num_layers // every
            grouped = jax.tree.map(
                lambda a: a.reshape(n_groups, every, *a.shape[1:]),
                params["layers"])

            def inner(h, layer):
                h = constrain(h, "batch", "seq", None)
                h, _ = M.apply_mamba_block(layer, cfg, h)
                return h, None
            inner = self._maybe_remat(inner)
            shared = params["shared_attn"]
            attn_fn = self._maybe_remat(
                lambda h: L.apply_dense_block(shared, cfg, h, positions))
            for g in range(n_groups):
                group = jax.tree.map(lambda a, g=g: a[g], grouped)
                x, _ = scan_over(cfg, inner, x, group)
                x = attn_fn(x)
            return x, jnp.zeros((), jnp.float32)

        raise ValueError(fam)

    def _encoder(self, params: Params, frames: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, T, _ = frames.shape
        pos = _positions(B, T)

        def body(h, layer):
            return L.apply_dense_block(layer, cfg, h, pos), None  # causal=False below
        # encoder is bidirectional: reuse dense block but non-causal attn
        def body_nc(h, layer):
            h = constrain(h, "batch", "seq", None)
            r = cfg.residual_scale
            a = L.apply_attention(layer["attn"], cfg,
                                  L.rms_norm(h, layer["norm1"], cfg.norm_eps),
                                  pos, causal=False)
            h = h + r * a
            h = h + r * L.apply_mlp(layer["mlp"],
                                    L.rms_norm(h, layer["norm2"], cfg.norm_eps))
            return h, None
        body_nc = self._maybe_remat(body_nc)
        x = frames.astype(cfg.act_dtype())
        x, _ = scan_over(cfg, body_nc, x, params["enc_layers"])
        return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _decoder(self, params: Params, tokens: jax.Array, enc_out: jax.Array
                 ) -> jax.Array:
        cfg = self.cfg
        B, S = tokens.shape
        pos = _positions(B, S)
        x = L.embed(params["embed"], cfg, tokens)

        def body(h, layer):
            h = constrain(h, "batch", "seq", None)
            a = L.apply_attention(layer["self_attn"], cfg,
                                  L.rms_norm(h, layer["norm1"], cfg.norm_eps), pos)
            h = h + a
            kv = L.encoder_kv(layer["cross_attn"], cfg, enc_out)
            ca = L.apply_cross_attention(layer["cross_attn"], cfg,
                                         L.rms_norm(h, layer["norm2"], cfg.norm_eps),
                                         kv)
            h = h + ca
            h = h + L.apply_mlp(layer["mlp"],
                                L.rms_norm(h, layer["norm3"], cfg.norm_eps))
            return h, None
        body = self._maybe_remat(body)
        x, _ = scan_over(cfg, body, x, params["layers"])
        return x

    def forward(self, params: Params, batch: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, jax.Array]:
        """Teacher-forcing logits. Returns (logits, aux_loss)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            enc_out = self._encoder(params, batch["frames"])
            x = self._decoder(params, batch["tokens"], enc_out)
            aux = jnp.zeros((), jnp.float32)
        else:
            tokens = batch["tokens"]
            x = constrain(L.embed(params["embed"], cfg, tokens),
                          "batch", "seq", None)
            offset = 0
            if cfg.family == "vlm":
                patches = batch["patches"].astype(cfg.act_dtype())
                x = jnp.concatenate([patches, x], axis=1)
                offset = patches.shape[1]
            B, S = x.shape[:2]
            pos = _positions(B, S)
            x, aux = self._backbone(params, x, pos)
            if offset:
                x = x[:, offset:, :]
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = constrain(L.unembed(params["embed"], cfg, x),
                           "batch", None, "tensor")
        return logits, aux

    def loss(self, params: Params, batch: Dict[str, jax.Array]
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, aux = self.forward(params, batch)
        ce, acc = cross_entropy(logits, batch["labels"])
        loss = ce + 0.01 * aux
        return loss, {"loss": loss, "ce": ce, "aux": aux, "accuracy": acc}

    # ------------------------------------------------------------- serving

    def _check_contiguous(self):
        if self.cfg.is_mla:
            raise ValueError(
                f"{self.cfg.name}: latent attention (MLA) is served from the "
                f"latent page slab only (init_paged_cache, "
                f"prefill_paged_chunk, decode_paged); the contiguous "
                f"cache holds keys and values per kv head")

    def init_cache(self, batch_size: int, max_len: int,
                   enc_len: int = 0) -> Dict[str, Any]:
        """Abstract/zeroed cache pytree for decode."""
        cfg = self.cfg
        self._check_contiguous()
        dt = cfg.act_dtype()
        B, Lc = batch_size, cfg.num_layers
        K, hd = cfg.num_kv_heads, cfg.hd
        fam = cfg.family
        cache: Dict[str, Any] = {"lengths": jnp.zeros((B,), jnp.int32)}
        if fam in ("dense", "vlm", "moe"):
            cache["k"] = jnp.zeros((Lc, B, max_len, K, hd), dt)
            cache["v"] = jnp.zeros((Lc, B, max_len, K, hd), dt)
        elif fam == "ssm":
            H, shd = cfg.ssm_heads, cfg.ssm_head_dim
            D = cfg.d_model
            cache.update(
                wkv=jnp.zeros((Lc, B, H, shd, shd), jnp.float32),
                tm_x=jnp.zeros((Lc, B, D), dt),
                cm_x=jnp.zeros((Lc, B, D), dt))
        elif fam == "hybrid":
            H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
            W, din = cfg.conv_width, cfg.d_inner
            n_groups = cfg.num_layers // (cfg.attn_every or cfg.num_layers)
            cache.update(
                conv=jnp.zeros((Lc, B, W - 1, din), dt),
                ssm=jnp.zeros((Lc, B, H, P, N), jnp.float32),
                attn_k=jnp.zeros((n_groups, B, max_len, K, hd), dt),
                attn_v=jnp.zeros((n_groups, B, max_len, K, hd), dt))
        elif fam == "encdec":
            cache["k"] = jnp.zeros((Lc, B, max_len, K, hd), dt)
            cache["v"] = jnp.zeros((Lc, B, max_len, K, hd), dt)
            cache["enc_k"] = jnp.zeros((Lc, B, enc_len, K, hd), dt)
            cache["enc_v"] = jnp.zeros((Lc, B, enc_len, K, hd), dt)
        return cache

    def prefill(self, params: Params, batch: Dict[str, jax.Array],
                max_len: int) -> Tuple[jax.Array, Dict[str, Any]]:
        """Process a full prompt; returns (last-position logits, cache)."""
        cfg = self.cfg
        self._check_contiguous()
        fam = cfg.family
        if fam == "encdec":
            return self._prefill_encdec(params, batch, max_len)
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = L.embed(params["embed"], cfg, tokens)
        offset = 0
        if fam == "vlm":
            patches = batch["patches"].astype(cfg.act_dtype())
            x = jnp.concatenate([patches, x], axis=1)
            offset = patches.shape[1]
        Sp = x.shape[1]
        pos = _positions(B, Sp)
        cache = self.init_cache(B, max_len)

        if fam in ("dense", "vlm", "moe"):
            def body(h, xs):
                layer = xs
                if fam == "moe":
                    a = L.apply_attention_prefill(
                        layer["attn"], cfg,
                        L.rms_norm(h, layer["norm1"], cfg.norm_eps), pos)
                    h = h + a[0]
                    mo, _ = X.apply_moe_held(
                        layer["moe"], cfg,
                        L.rms_norm(h, layer["norm2"], cfg.norm_eps))
                    h = h + mo
                    kv = a[1]
                else:
                    h, kv = L.apply_dense_block_prefill(layer, cfg, h, pos)
                return h, kv
            x, (ks, vs) = scan_over(cfg, body, x, params["layers"])
            pad = max_len - Sp
            cache["k"] = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            cache["v"] = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
            cache["lengths"] = jnp.full((B,), Sp, jnp.int32)
        elif fam == "ssm":
            def body(h, layer):
                h, st = R.apply_rwkv_block(layer, cfg, h)
                return h, st
            x, st = scan_over(cfg, body, x, params["layers"])
            cache.update(wkv=st["wkv"], tm_x=st["tm_x"], cm_x=st["cm_x"])
            cache["lengths"] = jnp.full((B,), Sp, jnp.int32)
        elif fam == "hybrid":
            every = cfg.attn_every or cfg.num_layers
            n_groups = cfg.num_layers // every
            grouped = jax.tree.map(
                lambda a: a.reshape(n_groups, every, *a.shape[1:]),
                params["layers"])
            convs, ssms, aks, avs = [], [], [], []
            for g in range(n_groups):
                group = jax.tree.map(lambda a, g=g: a[g], grouped)

                def inner(h, layer):
                    h, st = M.apply_mamba_block(layer, cfg, h)
                    return h, st
                x, st = scan_over(cfg, inner, x, group)
                convs.append(st["conv"])
                ssms.append(st["ssm"])
                blk = params["shared_attn"]
                a, kv = L.apply_attention_prefill(
                    blk["attn"], cfg,
                    L.rms_norm(x, blk["norm1"], cfg.norm_eps), pos)
                x = x + a
                x = x + L.apply_mlp(blk["mlp"],
                                    L.rms_norm(x, blk["norm2"], cfg.norm_eps))
                pad = max_len - Sp
                aks.append(jnp.pad(kv[0], ((0, 0), (0, pad), (0, 0), (0, 0))))
                avs.append(jnp.pad(kv[1], ((0, 0), (0, pad), (0, 0), (0, 0))))
            cache.update(conv=jnp.concatenate(convs), ssm=jnp.concatenate(ssms),
                         attn_k=jnp.stack(aks), attn_v=jnp.stack(avs),
                         lengths=jnp.full((B,), Sp, jnp.int32))
        x = L.rms_norm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params["embed"], cfg, x)[:, 0]
        return logits, cache

    def _prefill_encdec(self, params, batch, max_len):
        cfg = self.cfg
        enc_out = self._encoder(params, batch["frames"])
        B = enc_out.shape[0]
        # precompute per-layer cross-attention KV from the encoder output
        def kv_body(_, layer):
            return None, L.encoder_kv(layer["cross_attn"], cfg, enc_out)
        _, (eks, evs) = scan_over(cfg, kv_body, None, params["layers"])
        cache = self.init_cache(B, max_len, enc_len=enc_out.shape[1])
        cache["enc_k"], cache["enc_v"] = eks, evs
        # run the BOS token through decode to get first logits
        bos = batch.get("tokens", jnp.zeros((B, 1), jnp.int32))[:, :1]
        logits, cache = self.decode(params, cache, bos[:, 0])
        return logits, cache

    # ---------------------------------------------------- paged serving
    # Continuous-batching entry points (serve/engine.py). The KV cache is
    # a single page slab shared by every serving slot; per-slot page
    # tables map token position t to (table[t // page], t % page). Page 0
    # is reserved as the null page. Only KV-cache families support this.
    # The layer loop carries the slab whole and writes each layer's new
    # K/V into it in place: handing a layer's slice through the scan as
    # xs/ys would slice and write back the slab on every layer.

    def _check_paged(self):
        if self.cfg.family not in ("dense", "vlm", "moe"):
            raise ValueError(
                f"paged serving requires a KV-cache family, "
                f"got {self.cfg.family!r}")

    def check_page_size(self, page_size: int) -> None:
        """Raise ValueError unless the paged decode kernel this model
        runs (``latent_decode_paged`` for latent attention, else
        ``flash_decode_paged``) can take pages of ``page_size``."""
        if self.cfg.is_mla:
            ops.check_latent_page_size(page_size)
        else:
            ops.check_page_size(page_size, self.cfg.num_kv_heads,
                                self.cfg.hd)

    def decode_block_rows(self, page_size: int) -> int:
        """Positions of one slot that one step of the paged decode
        kernel's walk over live blocks attends, from the slab's lanes."""
        slab = jax.tree.leaves(jax.eval_shape(
            lambda: self.init_paged_cache(1, page_size)))[0]
        return ops.paged_block_rows(page_size, slab.shape[-1])

    def init_paged_cache(self, num_pages: int, page_size: int
                         ) -> Dict[str, jax.Array]:
        """Zeroed page slab. GQA/MHA: {'k_pages','v_pages': [L, P, page,
        K * hd]}, the kv heads side by side on the lane-dense minor dim.
        Latent attention: {'kv_pages': [L, P, page, lanes]}, one latent
        row per token (``kv_lora_rank`` latent lanes, then the rope key,
        zero-padded to whole 128-lane tiles)."""
        self._check_paged()
        cfg = self.cfg
        dt = cfg.act_dtype()
        if cfg.is_mla:
            return {"kv_pages": jnp.zeros(
                (cfg.num_layers, num_pages, page_size, L.latent_lanes(cfg)),
                dt)}
        shape = (cfg.num_layers, num_pages, page_size,
                 cfg.num_kv_heads * cfg.hd)
        return {"k_pages": jnp.zeros(shape, dt),
                "v_pages": jnp.zeros(shape, dt)}

    def _layers(self, params: Params, x: jax.Array, pages: Dict, attend,
                live: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict, jax.Array]:
        """Run the stacked layers, the slab carried whole: first the
        leading dense layers (``params['dense_layers']``, if any), then
        ``params['layers']``, the layer index running on across both.
        ``attend(attn_params, h, pages, layer) -> (a, pages)`` is a
        layer's attention, against the slab on the paged entry points
        (the latent model's full-sequence forward passes no slab and
        attends the sequence itself). MoE layers run the served,
        dropless routed layer; ``live`` ([B, S] bool) marks the tokens
        whose (token, held expert) assignments are counted. Returns
        ``(x, pages, held)``, ``held`` that count over the layers."""
        cfg = self.cfg
        # the moe block has no residual scale, as in ``_backbone``
        r = 1.0 if cfg.family == "moe" else cfg.residual_scale

        def mlp(layer, hn):
            return L.apply_mlp(layer["mlp"], hn), 0

        def moe(layer, hn):
            return X.apply_moe_held(layer["moe"], cfg, hn, live)

        def run(carry, layers, first, ffn):
            def body(carry, xs):
                h, pg, held = carry
                layer, i = xs
                a, pg = attend(layer["attn"], L.rms_norm(
                    h, layer["norm1"], cfg.norm_eps), pg, i)
                h = h + r * a
                mo, n = ffn(layer, L.rms_norm(h, layer["norm2"],
                                              cfg.norm_eps))
                return (h + r * mo, pg, held + n), None

            n_layers = jax.tree.leaves(layers)[0].shape[0]
            idx = first + jnp.arange(n_layers, dtype=jnp.int32)
            carry, _ = scan_over(cfg, body, carry, (layers, idx))
            return carry

        carry = (x, pages, jnp.zeros((), jnp.int32))
        n_dense = 0
        if "dense_layers" in params:
            carry = run(carry, params["dense_layers"], 0, mlp)
            n_dense = cfg.first_dense_layers
        return run(carry, params["layers"], n_dense,
                   moe if cfg.family == "moe" else mlp)

    def decode_paged(self, params: Params, pages: Dict[str, jax.Array],
                     tokens: jax.Array, page_tables: jax.Array,
                     lengths: jax.Array, slot_mask: jax.Array):
        """One decode step over the page slab.

        tokens: [B] int32; page_tables: [B, M] int32; lengths: [B]
        (cache entries already written; the new token lands at position
        ``lengths``); slot_mask: [B] bool — idle slots write to the null
        page and produce garbage logits the engine ignores.
        Returns ([B, V] logits, new pages, held): ``held`` the live
        slots' (token, held expert) assignments over the layers, a
        constant 0 for a model without routed experts.
        """
        self._check_paged()
        cfg = self.cfg
        x = L.embed(params["embed"], cfg, tokens[:, None])
        rows = L.paged_decode_rows(page_tables, lengths, slot_mask,
                                   jax.tree.leaves(pages)[0].shape[2])

        if cfg.is_mla:
            def attend(p, h, pages, i):
                a, kv = L.apply_mla_decode_paged(
                    p, cfg, h, pages["kv_pages"], i, page_tables, lengths,
                    rows)
                return a, {"kv_pages": kv}
        else:
            def attend(p, h, pages, i):
                a, kp, vp = L.apply_attention_decode_paged(
                    p, cfg, h, pages["k_pages"], pages["v_pages"], i,
                    page_tables, lengths, rows)
                return a, {"k_pages": kp, "v_pages": vp}

        x, pages, held = self._layers(params, x, pages, attend,
                                      live=slot_mask[:, None])
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params["embed"], cfg, x)[:, 0]
        return logits, pages, held

    def prefill_paged_chunk(self, params: Params,
                            pages: Dict[str, jax.Array],
                            tokens: jax.Array, page_table: jax.Array,
                            start: jax.Array, n_valid: jax.Array
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Prefill ONE request's next prompt chunk into the slab.

        tokens: [1, C] padded to the static chunk length; page_table:
        [M] (this request's row); start: tokens already cached; n_valid:
        real tokens in this chunk (traced — one compile covers every
        chunk including the ragged tail). Returns ([1, V] logits at the
        chunk's last VALID position, new pages).
        """
        self._check_paged()
        cfg = self.cfg
        x = L.embed(params["embed"], cfg, tokens)

        if cfg.is_mla:
            def attend(p, h, pages, i):
                a, kv = L.apply_mla_prefill_paged(
                    p, cfg, h, pages["kv_pages"], i, page_table, start,
                    n_valid)
                return a, {"kv_pages": kv}
        else:
            def attend(p, h, pages, i):
                a, kp, vp = L.apply_attention_prefill_paged(
                    p, cfg, h, pages["k_pages"], pages["v_pages"], i,
                    page_table, start, n_valid)
                return a, {"k_pages": kp, "v_pages": vp}

        x, pages, _ = self._layers(params, x, pages, attend)
        last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, axis=1)
        last = L.rms_norm(last, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params["embed"], cfg, last)[:, 0]
        return logits, pages

    def decode(self, params: Params, cache: Dict[str, Any],
               tokens: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
        """One decode step. tokens: [B] int32. Returns ([B, V] logits, cache)."""
        cfg = self.cfg
        self._check_contiguous()
        fam = cfg.family
        B = tokens.shape[0]
        lengths = cache["lengths"]
        x = L.embed(params["embed"], cfg, tokens[:, None])

        if fam in ("dense", "vlm", "moe"):
            def body(h, xs):
                layer, ck, cv = xs
                if fam == "moe":
                    a, nk, nv = L.apply_attention_decode(
                        layer["attn"], cfg,
                        L.rms_norm(h, layer["norm1"], cfg.norm_eps),
                        ck, cv, lengths)
                    h = h + a
                    mo, _ = X.apply_moe_held(
                        layer["moe"], cfg,
                        L.rms_norm(h, layer["norm2"], cfg.norm_eps))
                    h = h + mo
                else:
                    h, nk, nv = L.apply_dense_block_decode(
                        layer, cfg, h, ck, cv, lengths)
                return h, (nk, nv)
            x, (nks, nvs) = scan_over(cfg, 
                body, x, (params["layers"], cache["k"], cache["v"]))
            cache = dict(cache, k=nks, v=nvs, lengths=lengths + 1)
        elif fam == "ssm":
            def body(h, xs):
                layer, wkv, tm_x, cm_x = xs
                h, st = R.apply_rwkv_block(
                    layer, cfg, h, {"wkv": wkv, "tm_x": tm_x, "cm_x": cm_x})
                return h, (st["wkv"], st["tm_x"], st["cm_x"])
            x, (wkv, tm_x, cm_x) = scan_over(cfg, 
                body, x, (params["layers"], cache["wkv"], cache["tm_x"],
                          cache["cm_x"]))
            cache = dict(cache, wkv=wkv, tm_x=tm_x, cm_x=cm_x,
                         lengths=lengths + 1)
        elif fam == "hybrid":
            every = cfg.attn_every or cfg.num_layers
            n_groups = cfg.num_layers // every
            grouped = jax.tree.map(
                lambda a: a.reshape(n_groups, every, *a.shape[1:]),
                params["layers"])
            conv = cache["conv"].reshape(n_groups, every, *cache["conv"].shape[1:])
            ssm = cache["ssm"].reshape(n_groups, every, *cache["ssm"].shape[1:])
            new_conv, new_ssm, new_ak, new_av = [], [], [], []
            for g in range(n_groups):
                group = jax.tree.map(lambda a, g=g: a[g], grouped)

                def inner(h, xs):
                    layer, cv_, sm_ = xs
                    h, st = M.apply_mamba_block(
                        layer, cfg, h, {"conv": cv_, "ssm": sm_})
                    return h, (st["conv"], st["ssm"])
                x, (cvs, sms) = scan_over(cfg, inner, x, (group, conv[g], ssm[g]))
                new_conv.append(cvs)
                new_ssm.append(sms)
                blk = params["shared_attn"]
                a, nk, nv = L.apply_attention_decode(
                    blk["attn"], cfg,
                    L.rms_norm(x, blk["norm1"], cfg.norm_eps),
                    cache["attn_k"][g], cache["attn_v"][g], lengths)
                x = x + a
                x = x + L.apply_mlp(blk["mlp"],
                                    L.rms_norm(x, blk["norm2"], cfg.norm_eps))
                new_ak.append(nk)
                new_av.append(nv)
            cache = dict(cache,
                         conv=jnp.concatenate(new_conv), ssm=jnp.concatenate(new_ssm),
                         attn_k=jnp.stack(new_ak), attn_v=jnp.stack(new_av),
                         lengths=lengths + 1)
        elif fam == "encdec":
            def body(h, xs):
                layer, ck, cv, ek, ev = xs
                a, nk, nv = L.apply_attention_decode(
                    layer["self_attn"], cfg,
                    L.rms_norm(h, layer["norm1"], cfg.norm_eps),
                    ck, cv, lengths)
                h = h + a
                ca = L.apply_cross_attention(
                    layer["cross_attn"], cfg,
                    L.rms_norm(h, layer["norm2"], cfg.norm_eps), (ek, ev))
                h = h + ca
                h = h + L.apply_mlp(layer["mlp"],
                                    L.rms_norm(h, layer["norm3"], cfg.norm_eps))
                return h, (nk, nv)
            x, (nks, nvs) = scan_over(cfg, 
                body, x, (params["layers"], cache["k"], cache["v"],
                          cache["enc_k"], cache["enc_v"]))
            cache = dict(cache, k=nks, v=nvs, lengths=lengths + 1)
        else:
            raise ValueError(fam)

        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = constrain(L.unembed(params["embed"], cfg, x),
                           "batch", None, "tensor")[:, 0]
        return logits, cache


@functools.lru_cache(maxsize=None)
def _cached_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def build_model(cfg: ModelConfig) -> Model:
    return _cached_model(cfg)
