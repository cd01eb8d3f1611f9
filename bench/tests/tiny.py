"""A tiny cell on the CPU: a benchmark root in a temporary directory.

``make_root(tmp)`` writes a ``BENCHMARK.json`` with one cell, a two-layer
qwen2-type model of width 64 in bf16 (the same family files, reference
and metric readers as the real cells, copied in) and a fast open-loop
mix, so that the whole harness, its child processes and the check run
in seconds without a chip.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CELL = "tiny.chat"

CONFIG = {
    "source": "test", "model_type": "qwen2", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "engine": {"max_slots": 4, "max_len": 64, "prefill_chunk": 16,
               "queue_maxsize": 16},
}
MIX = {"loop": "open", "arrivals": "poisson", "rate_per_s": 12.0,
       "prompt": {"median": 12, "sigma": 0.8, "min": 2, "max": 40},
       "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 16},
       "block": 8, "preroll_s": 0.5}
#: the bf16 engine reads 0.0 against the f32 reference at this size, the
#: fp8 control about 0.06
LIMITS = {"max_logit_gap": 0.02, "wrong_replies": 0, "missing_replies": 0,
          "duplicate_replies": 0, "decode_compiles": 1,
          "prefill_compiles": 1}


def make_root(tmp: Path, extra_metrics=()) -> Path:
    root = Path(tmp)
    b = root / "bench"
    for sub in ("families", "reference", "metrics"):
        shutil.copytree(BENCH / sub, b / sub)
    for sub in ("configs", "traffic", "limits"):
        (b / sub).mkdir(parents=True)
    (b / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "chat.json").write_text(json.dumps(MIX))
    (b / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec = {
        "command": real["command"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": CELL, "config": "tiny", "traffic": "chat",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[CELL]) for m in real["per_layer"]]
        + list(extra_metrics),
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
