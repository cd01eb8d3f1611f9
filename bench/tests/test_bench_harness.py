"""The whole harness on the CPU at a tiny size: three processes, the
engine loop, the readers found by name, the check that decides
``correct``, and the command's refusal to run without a chip."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench.harness import run_cell
from bench.tests import tiny

REPO = Path(__file__).resolve().parents[2]

DUMMY = '''"""A metric added as a file of its own: the working ticks traced."""


def read(run):
    return float(sum(t["worked"] for t in run.ticks))
'''


def test_tiny_cell_is_correct_and_finds_a_new_metric_by_name(tmp_path):
    entry = {"name": "dummy_ticks", "unit": "ticks", "better": "higher",
             "source": "program_counter", "layer": "scheduler",
             "moves": "tpot_p90_ms", "workloads": [tiny.CELL]}
    root = tiny.make_root(tmp_path, extra_metrics=[entry])
    (root / "bench" / "metrics" / "dummy_ticks.py").write_text(DUMMY)
    res = run_cell(root, tiny.CELL, 2**33 + 1, 2.0, True, time.time(),
                   require_tpu=False, compile_cache=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 10 and res["failed"] == 0
    assert res["metrics"]["dummy_ticks"]["value"] > 0
    assert res["metrics"]["tokens_per_decode_step.chat"]["value"] >= 1
    # no TPU plane: the device readers find nothing and stay out
    assert "decode_step_ms" not in res["metrics"]
    assert list(res)[-1] == "checks"


def test_control_run_is_not_correct(tmp_path):
    """The fp8 reference's first choices, judged in the served tokens'
    place, fail the limit that the served tokens pass."""
    root = tiny.make_root(tmp_path)
    res = run_cell(root, tiny.CELL, 2**31 + 9, 2.0, False, time.time(),
                   require_tpu=False, control=True, compile_cache=False)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"] >= res["info"]["served_logit_gap"]
    assert all(c["value"] <= c["limit"] for k, c in res["checks"].items()
               if k != "max_logit_gap"), res["checks"]


def test_per_layer_metric_moving_an_unreported_metric_is_an_error(tmp_path):
    from bench.registry import Registry

    entry = {"name": "stray", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "scheduler",
             "moves": "output_tok_s", "workloads": [tiny.CELL]}
    root = tiny.make_root(tmp_path, extra_metrics=[entry])
    with pytest.raises(ValueError, match="stray"):
        Registry(root).per_layer(tiny.CELL)


def test_tiny_cell_end_to_end_metrics(tmp_path):
    root = tiny.make_root(tmp_path)
    res = run_cell(root, tiny.CELL, 12, 2.0, False, time.time(),
                   require_tpu=False, compile_cache=False)
    assert res["correct"], res["checks"]
    spec = json.loads((root / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def _run_py(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen1.5-0.5b.chat-poisson", "--seed", "1", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    p = _run_py(REPO, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    import shutil

    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for d in spec["paths"]:
        shutil.copytree(REPO / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = _run_py(tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
