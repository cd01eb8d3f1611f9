"""The plain reference against the program's own ``Model.forward`` at the
SMOKE size (float32 on the CPU), through the benchmark's weights and its
mapping onto the program's parameters; and the gap arithmetic and the
fp8 control that ``correct`` rests on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.families import qwen2 as fam
from bench.harness import seed_key
from bench.reference import qwen2 as ref

SMOKE = {"name": "smoke", "model_type": "qwen2", "hidden_size": 64,
         "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_hidden_layers": 2,
         "vocab_size": 256, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": True, "torch_dtype": "float32"}


def _program_logits(cfg, weights, tokens):
    from repro.models.model import build_model

    model = build_model(fam.program_config(cfg))
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.forward(fam.program_params(w32),
                                        {"tokens": tokens[None]})[0][0])


@pytest.mark.parametrize("tied,kv_heads", [(True, 4), (False, 4),
                                           (True, 2)])
def test_reference_matches_program_forward(tied, kv_heads):
    cfg = dict(SMOKE, tie_word_embeddings=tied, num_key_value_heads=kv_heads)
    w = fam.init_weights(cfg, seed_key(5))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, 48),
                         jnp.int32)
    want = _program_logits(cfg, w, tokens)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref._logits(w, dict(ref.consts(cfg)), tokens,
                                     False))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_gaps_are_zero_at_the_argmax_and_positive_elsewhere():
    w = fam.init_weights(SMOKE, seed_key(6))
    tokens = jnp.asarray(np.arange(1, 33), jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref._logits(w, dict(ref.consts(SMOKE)), tokens,
                                        False))
    best = logits.argmax(-1)
    other = (best + 1) % 256
    g = np.asarray(ref.gaps(w, SMOKE, tokens,
                            jnp.asarray(np.stack([best, other]))))
    assert np.all(g[0] == 0)
    assert np.allclose(g[1], logits.max(-1) - logits[np.arange(32), other])
    assert np.all(g[1] > 0)


def test_fp8_control_departs_from_the_reference():
    """The control rounds every product's operands to fp8: at some
    positions it puts another token first, and that token lies a gap
    below the reference's best that bf16 rounding does not reach."""
    cfg = dict(SMOKE, torch_dtype="bfloat16")
    w = fam.init_weights(cfg, seed_key(7))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, 64),
                         jnp.int32)
    c = np.asarray(ref.control_argmax(w, cfg, tokens))
    g = np.asarray(ref.gaps(w, cfg, tokens, jnp.asarray(c[None])))[0]
    assert (g > 0).sum() >= 4
    assert g.max() > 0.02
