"""``scripts/moe_gap_check.py`` at a tiny deepseek-v3-type size on the
CPU: each computation's reading is made as ``bench/check.py`` makes a
served token's, so the f32 reference judged by itself reads nothing,
and the readings have the shape the script reports."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from moe_gap_check import measure  # noqa: E402

from bench.tests import tiny_mla  # noqa: E402

NAMES = ("f32", "program", "ref_bf16", "ref_bf16_pinned",
         "ref_bf16_routes_f32", "fp8")


@pytest.fixture(scope="module")
def readings():
    cfg = dict(tiny_mla.CONFIG, name="tiny-mla", torch_dtype="bfloat16")
    return measure(cfg, [2**32 + 7, 11], 48)


def test_every_computation_is_read_on_every_seed(readings):
    assert set(readings["seeds"]) == {str(2**32 + 7), "11"}
    for res in readings["seeds"].values():
        for name in NAMES:
            r = res[name]
            assert 0.0 <= r["median"] <= r["p90"] <= r["max"]
            assert 0.0 <= r["not_argmax"] <= 1.0


def test_the_reference_reads_nothing_against_itself(readings):
    for res in readings["seeds"].values():
        assert res["f32"]["max"] == 0.0
        assert res["f32"]["not_argmax"] == 0.0


def test_route_flips_are_counted_per_moe_layer(readings):
    moe_layers = (tiny_mla.CONFIG["num_hidden_layers"]
                  - tiny_mla.CONFIG["first_k_dense_replace"])
    for res in readings["seeds"].values():
        flips = res["route_flips_ref_bf16"]
        assert len(flips["per_layer"]) == moe_layers
        assert 0.0 <= flips["pairs"] <= flips["tokens_with_any"] <= 1.0


def test_the_fp8_control_departs(readings):
    assert max(res["fp8"]["max"] for res in readings["seeds"].values()) > 0
