"""The controls of ``scripts/kernel_controls.py``: its online softmax in
f32 is the oracle's attention, and rounding its accumulator or running
sum to bf16 moves it, so those controls test what they name."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))

from kernel_controls import online_attention, prefill_scores  # noqa: E402

from repro.kernels import ref  # noqa: E402

S, H, D, BLOCK = 40, 2, 16, 8


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    return [jax.random.normal(k, (1, S, H, D), jnp.float32) for k in ks]


def _online(qkv, **kw):
    q, k, v = qkv
    out = online_attention(prefill_scores(q, k), v[0].transpose(1, 0, 2),
                           BLOCK, **kw)
    return np.asarray(out.transpose(1, 0, 2)[None])


def test_online_f32_matches_oracle(qkv):
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.attention(*qkv))
    np.testing.assert_allclose(_online(qkv), want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kw", [{"acc_bf16": True}, {"l_bf16": True}])
def test_bf16_rounding_is_not_folded_away(qkv, kw):
    err = np.abs(_online(qkv, **kw) - _online(qkv)).max()
    assert 1e-4 < err < 5e-2
