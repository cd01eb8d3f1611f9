"""Unified model configuration for the 10 assigned architectures."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | encdec
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: Optional[int] = None          # default d_model // num_heads
    qkv_bias: bool = False                  # qwen1.5
    tie_embeddings: bool = False

    # MoE
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0                       # per-expert hidden dim
    num_shared_experts: int = 0             # kimi-k2 style shared expert
    capacity_factor: float = 1.25
    moe_impl: str = "dense"                 # "dense" (GShard einsum) | "gather"
    router_score: str = "softmax"           # softmax | sigmoid (deepseek-v3:
                                            # a bias added for the choice only)
    routed_scale: float = 1.0               # deepseek-v3 routed_scaling_factor
    first_dense_layers: int = 0             # leading layers with a d_ff MLP
    # the chip's expert share: experts [expert_offset, expert_offset +
    # experts_held) of the router's num_experts live here (0: all of them)
    experts_held: int = 0
    expert_offset: int = 0

    # latent attention (MLA, deepseek-v3), on when kv_lora_rank > 0: keys
    # and values come from one kv_lora_rank-wide latent plus one rope key
    # shared by all heads
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM (rwkv6 / mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2                     # mamba2 d_inner = expand * d_model
    conv_width: int = 4

    # hybrid (zamba2): shared attention block applied every `attn_every`
    attn_every: int = 0

    # enc-dec (seamless)
    encoder_layers: int = 0

    # modality frontend stubs (vlm / audio)
    num_prefix_embeddings: int = 0          # patch/frame embeddings prepended

    # scaling / misc
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    emb_scale: float = 1.0                  # minicpm scale_emb
    residual_scale: float = 1.0             # minicpm scale_depth / sqrt(L)
    logit_soft_cap: Optional[float] = None

    # numerics
    dtype: str = "bfloat16"                 # activation dtype
    param_dtype: str = "bfloat16"

    # training-time structure
    remat: str = "full"                     # none | full
    scan_layers: bool = True

    # serving-time structure
    decode_cache_update: str = "onehot"     # onehot | dynamic (see layers)

    # --- derived -----------------------------------------------------------

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.num_heads

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the experts this chip holds."""
        return self.expert_offset, self.experts_held or self.num_experts

    @property
    def d_inner(self) -> int:               # mamba2
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:             # mamba2 / rwkv6 heads
        if self.family == "ssm":            # rwkv6: heads over d_model
            return self.d_model // self.ssm_head_dim
        return self.d_inner // self.ssm_head_dim

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    def act_dtype(self):
        return jnp.dtype(self.dtype)

    def p_dtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # --- parameter counting (for 6·N·D roofline bookkeeping) ---------------

    def param_counts(self) -> Tuple[int, int]:
        """(total_params, active_params_per_token). Embeddings included in
        total; active excludes the non-routed experts."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        H, K, hd = self.num_heads, self.num_kv_heads, self.hd

        def attn_params() -> int:
            if self.is_mla:
                R, rp = self.kv_lora_rank, self.qk_rope_head_dim
                nope, v = self.qk_nope_head_dim, self.v_head_dim
                return (D * H * (nope + rp) + D * (R + rp) + R
                        + R * H * (nope + v) + H * v * D)
            p = D * H * hd + 2 * D * K * hd + H * hd * D
            if self.qkv_bias:
                p += H * hd + 2 * K * hd
            return p

        def mlp_params(f: int) -> int:
            return 3 * D * f  # swiglu: wi, wg, wo

        emb = V * D + (0 if self.tie_embeddings else D * V)
        total = emb
        active = emb

        if self.family in ("dense", "vlm"):
            per_layer = attn_params() + mlp_params(F) + 2 * D
            total += L * per_layer
            active += L * per_layer
        elif self.family == "moe":
            e_all = self.num_experts * 3 * D * self.moe_d_ff
            e_act = (self.experts_per_token + self.num_shared_experts) * 3 * D * self.moe_d_ff
            router = D * self.num_experts
            if self.router_score == "sigmoid":
                router += self.num_experts          # the choice bias
            shared = self.num_shared_experts * 3 * D * self.moe_d_ff
            per_layer_total = attn_params() + e_all + shared + router + 2 * D
            per_layer_active = attn_params() + e_act + router + 2 * D
            n_dense = self.first_dense_layers
            dense = attn_params() + mlp_params(F) + 2 * D
            total += (L - n_dense) * per_layer_total + n_dense * dense
            active += (L - n_dense) * per_layer_active + n_dense * dense
        elif self.family == "ssm":  # rwkv6
            Hh, hdh = self.ssm_heads, self.ssm_head_dim
            tm = 5 * D * D + D * D + 2 * 64 * D + Hh * hdh + 5 * D  # r,k,v,g,o + decay lora + u + mus
            cm = 2 * D * F // 2 + D * D  # rwkv channel mix (k, v, r)
            per_layer = tm + cm + 2 * D
            total += L * per_layer
            active += L * per_layer
        elif self.family == "hybrid":  # zamba2
            din, N, Hh = self.d_inner, self.ssm_state, self.ssm_heads
            in_proj = D * (2 * din + 2 * N + Hh)
            per_layer = in_proj + self.conv_width * din + din * D + Hh + Hh + 2 * D
            total += L * per_layer
            active += L * per_layer
            shared_attn = attn_params() + mlp_params(F) + 2 * D
            total += shared_attn
            active += shared_attn
        elif self.family == "encdec":
            enc = self.encoder_layers * (attn_params() + mlp_params(F) + 2 * D)
            dec = L * (2 * attn_params() + mlp_params(F) + 3 * D)
            total += enc + dec
            active += enc + dec
        return int(total), int(active)


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell from the assignment."""

    name: str                   # train_4k | prefill_32k | decode_32k | long_500k
    kind: str                   # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}

#: archs whose `long_500k` cell is skipped (pure full-attention families)
LONG_CONTEXT_ARCHS = ("rwkv6-7b", "zamba2-2.7b")
