"""DeepSeek-V3-type models (Moonlight): seeded weights and the program's view.

The benchmark, not the program, makes the weights, so that the plain
reference (``bench/reference/deepseek_v3.py``) can use them without
taking anything the program made. ``init_weights`` builds every leaf on
the device in one jitted call, in bfloat16 (the choice bias in float32),
stacked over layers, matrices oriented ``x @ W``. The leading dense
layers (``first_k_dense_replace``) carry the prefix ``d_``:

    embed [V, D]   lm_head [D, V]   final_norm [D]
    per layer:  ln1, ln2 [D]   wq [D, H*(nope+rope)]   wkv_a [D, R+rope]
                kv_norm [R]   wkv_b [R, H*(nope+v)]   wo [H*v, D]
    dense:      w_gate, w_up [D, F]   w_down [F, D]
    MoE:        router [D, E_router]   bias [E_router] (f32)
                e_gate, e_up [E_held, D, Fm]   e_down [E_held, Fm, D]
                s_gate, s_up [D, Fm*n_shared]  s_down [Fm*n_shared, D]

``E_router`` is the router's published width (``router_experts``);
``E_held`` the experts this chip holds (``n_routed_experts`` of the
configuration file, the first of them ``held_experts.first``). Matrices
are normal with std ``fan_in ** -0.5``, the embedding and head 0.02, the
choice bias 0.1, and norm weights ``1 + 0.1 * normal``, so that every
parameter shapes the logits. ``program_config`` and ``program_params``
map the configuration and these weights onto the program's
``ModelConfig`` and parameter tree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    R, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    Ld = cfg["first_k_dense_replace"]
    return dict(D=D, H=H, R=R, rope=rope, nope=cfg["qk_nope_head_dim"],
                vd=cfg["v_head_dim"], C=R + rope, F=cfg["intermediate_size"],
                Fm=cfg["moe_intermediate_size"], L=cfg["num_hidden_layers"],
                Ld=Ld, Lm=cfg["num_hidden_layers"] - Ld,
                V=cfg["vocab_size"], E_router=cfg["router_experts"],
                E_held=cfg["n_routed_experts"],
                first_held=cfg["held_experts"]["first"],
                k=cfg["num_experts_per_tok"],
                Fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"])


def shapes(cfg: dict) -> dict:
    d = dims(cfg)
    D, H, R, rp, nope, vd = (d[k] for k in "D H R rope nope vd".split())
    F, Fm, Fs, E, Eh = d["F"], d["Fm"], d["Fs"], d["E_router"], d["E_held"]

    def attn(n):
        return {"ln1": ((n, D), "norm"), "ln2": ((n, D), "norm"),
                "wq": ((n, D, H * (nope + rp)), D ** -0.5),
                "wkv_a": ((n, D, R + rp), D ** -0.5),
                "kv_norm": ((n, R), "norm"),
                "wkv_b": ((n, R, H * (nope + vd)), R ** -0.5),
                "wo": ((n, H * vd, D), (H * vd) ** -0.5)}

    s = {"embed": ((d["V"], D), 0.02), "lm_head": ((D, d["V"]), 0.02),
         "final_norm": ((D,), "norm")}
    dense = dict(attn(d["Ld"]), w_gate=((d["Ld"], D, F), D ** -0.5),
                 w_up=((d["Ld"], D, F), D ** -0.5),
                 w_down=((d["Ld"], F, D), F ** -0.5))
    s.update({"d_" + k: v for k, v in dense.items()})
    n = d["Lm"]
    s.update(attn(n), router=((n, D, E), D ** -0.5),
             bias=((n, E), "bias"),
             e_gate=((n, Eh, D, Fm), D ** -0.5),
             e_up=((n, Eh, D, Fm), D ** -0.5),
             e_down=((n, Eh, Fm, D), Fm ** -0.5),
             s_gate=((n, D, Fs), D ** -0.5), s_up=((n, D, Fs), D ** -0.5),
             s_down=((n, Fs, D), Fs ** -0.5))
    return s


@functools.lru_cache(maxsize=None)
def _init_fn(spec: tuple):
    def init(key):
        out = {}
        for i, (name, shape, std) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if std == "bias":
                out[name] = 0.1 * jax.random.normal(k, shape, jnp.float32)
                continue
            x = jax.random.normal(k, shape, jnp.bfloat16)
            out[name] = (1 + 0.1 * x) if std == "norm" else x * std
        return out
    return jax.jit(init)


def init_weights(cfg: dict, key) -> dict:
    spec = tuple((n, shape, std) for n, (shape, std)
                 in sorted(shapes(cfg).items()))
    return _init_fn(spec)(key)


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration."""
    from repro.models.config import ModelConfig

    d = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="moe", num_layers=d["L"], d_model=d["D"],
        num_heads=d["H"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=d["F"], vocab_size=d["V"], first_dense_layers=d["Ld"],
        num_experts=d["E_router"], experts_held=d["E_held"],
        expert_offset=d["first_held"], experts_per_token=d["k"],
        moe_d_ff=d["Fm"], num_shared_experts=cfg["n_shared_experts"],
        router_score=cfg["scoring_func"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        kv_lora_rank=d["R"], qk_nope_head_dim=d["nope"],
        qk_rope_head_dim=d["rope"], v_head_dim=d["vd"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"])


ATTN = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")


def program_params(w: dict) -> dict:
    """The same arrays under the program's parameter names (no copies)."""
    def block(p, ffn_key, ffn):
        return {"attn": {k: w[p + k] for k in ATTN}, ffn_key: ffn,
                "norm1": w[p + "ln1"], "norm2": w[p + "ln2"]}

    return {
        "embed": {"tok": w["embed"], "head": w["lm_head"]},
        "final_norm": w["final_norm"],
        "dense_layers": block("d_", "mlp", {
            "wg": w["d_w_gate"], "wi": w["d_w_up"], "wo": w["d_w_down"]}),
        "layers": block("", "moe", {
            "router": w["router"], "bias": w["bias"], "wg": w["e_gate"],
            "wi": w["e_up"], "wo": w["e_down"],
            "shared": {"wg": w["s_gate"], "wi": w["s_up"],
                       "wo": w["s_down"]}}),
    }
