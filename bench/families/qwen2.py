"""Qwen2-type dense models (Qwen1.5): seeded weights and the program's view.

The benchmark, not the program, makes the weights, so that the plain
reference (``bench/reference/qwen2.py``) can use them without taking
anything the program made. ``init_weights`` builds every leaf on the
device in one jitted call, in bfloat16, stacked over layers, with
matrices oriented ``x @ W``:

    embed [V, D]         lm_head [D, V] (untied only)   final_norm [D]
    ln1, ln2 [L, D]      wq [L, D, H*hd]  wk, wv [L, D, K*hd]
    bq [L, H*hd]  bk, bv [L, K*hd]        wo [L, H*hd, D]
    w_gate, w_up [L, D, F]               w_down [L, F, D]

Matrices are normal with std ``fan_in ** -0.5``, the embedding and head
0.02, biases 0.1, and norm weights ``1 + 0.1 * normal``, so that every
parameter shapes the logits. ``program_config`` and ``program_params``
map the published config and these weights onto the program's
``ModelConfig`` and parameter tree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(D=D, H=H, K=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", D // H), F=cfg["intermediate_size"],
                L=cfg["num_hidden_layers"], V=cfg["vocab_size"],
                tied=bool(cfg["tie_word_embeddings"]))


def shapes(cfg: dict) -> dict:
    d = dims(cfg)
    D, H, K, hd, F, L, V = (d[k] for k in "D H K hd F L V".split())
    s = {"embed": ((V, D), 0.02), "final_norm": ((D,), "norm"),
         "ln1": ((L, D), "norm"), "ln2": ((L, D), "norm"),
         "wq": ((L, D, H * hd), D ** -0.5), "bq": ((L, H * hd), 0.1),
         "wk": ((L, D, K * hd), D ** -0.5), "bk": ((L, K * hd), 0.1),
         "wv": ((L, D, K * hd), D ** -0.5), "bv": ((L, K * hd), 0.1),
         "wo": ((L, H * hd, D), (H * hd) ** -0.5),
         "w_gate": ((L, D, F), D ** -0.5), "w_up": ((L, D, F), D ** -0.5),
         "w_down": ((L, F, D), F ** -0.5)}
    if not d["tied"]:
        s["lm_head"] = ((D, V), 0.02)
    return s


@functools.lru_cache(maxsize=None)
def _init_fn(spec: tuple):
    def init(key):
        out = {}
        for i, (name, shape, std) in enumerate(spec):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.bfloat16)
            out[name] = (1 + 0.1 * x) if std == "norm" else x * std
        return out
    return jax.jit(init)


def init_weights(cfg: dict, key) -> dict:
    spec = tuple((n, shape, std) for n, (shape, std)
                 in sorted(shapes(cfg).items()))
    return _init_fn(spec)(key)


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for this published config."""
    from repro.models.config import ModelConfig

    d = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=d["L"],
        d_model=d["D"], num_heads=d["H"], num_kv_heads=d["K"],
        head_dim=d["hd"], d_ff=d["F"], vocab_size=d["V"], qkv_bias=True,
        tie_embeddings=d["tied"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        param_dtype=cfg["torch_dtype"])


def program_params(w: dict) -> dict:
    """The same arrays under the program's parameter names (no copies)."""
    embed = {"tok": w["embed"]}
    if "lm_head" in w:
        embed["head"] = w["lm_head"]
    return {"embed": embed, "final_norm": w["final_norm"],
            "layers": {"attn": {k: w[k] for k in
                                ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
                       "mlp": {"wg": w["w_gate"], "wi": w["w_up"],
                               "wo": w["w_down"]},
                       "norm1": w["ln1"], "norm2": w["ln2"]}}
