"""A tiny latent-attention MoE cell on the CPU, beside ``tiny.py``'s dense
one: ``make_root(tmp)`` writes a benchmark root whose one cell is a
three-layer deepseek-v3-type model of width 64 (one dense layer, two MoE
layers of 8 routed experts, top-3, this chip holding experts 2-5 of
them), with the real family, reference and metric readers, and every
per-layer metric of ``BENCHMARK.json``, the Moonlight cell's three among
them.

The engine runs in float32: in bfloat16 its activations flip near-ties
of the top-3 routing against the f32 reference, and one flipped expert
moves the widest logit gap from about 0.01 to 0.15 on some seeds, so a
bf16 cell's ``correct`` would depend on the seed (PERF.md section 2)."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench.tests import tiny

BENCH = tiny.BENCH
CELL = "tiny-mla.doc"

CONFIG = {
    "source": "test", "model_type": "deepseek_v3", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "n_routed_experts": 4,
    "router_experts": 8, "held_experts": {"first": 2, "count": 4},
    "scoring_func": "sigmoid", "routed_scaling_factor": 2.446,
    "vocab_size": 256, "rope_theta": 50000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "torch_dtype": "float32",
    "engine": {"max_slots": 4, "max_len": 64, "prefill_chunk": 16,
               "queue_maxsize": 16},
}
#: read at this size (CPU, f32 engine against the f32 reference): the
#: served tokens' widest gap 0.0 on three seeds, with one held expert
#: left out of the decode step 0.19-0.28
LIMITS = dict(tiny.LIMITS, max_logit_gap=0.02)


def make_root(tmp: Path) -> Path:
    root = Path(tmp)
    b = root / "bench"
    for sub in ("families", "reference", "metrics"):
        shutil.copytree(BENCH / sub, b / sub)
    for sub in ("configs", "traffic", "limits"):
        (b / sub).mkdir(parents=True)
    (b / "configs" / "tiny-mla.json").write_text(json.dumps(CONFIG))
    (b / "traffic" / "doc.json").write_text(json.dumps(tiny.MIX))
    (b / "limits" / f"{CELL}.json").write_text(
        json.dumps({"limits": LIMITS}))
    real = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec = {
        "command": real["command"], "paths": ["bench"], "run_seconds": 1,
        "configs": [{"name": "tiny-mla", "source": "test",
                     "file": "bench/configs/tiny-mla.json",
                     "reduced": ["n_routed_experts"], "why": "test"}],
        "workloads": [{"name": CELL, "config": "tiny-mla",
                       "traffic": "doc", "chips": 1, "why": "test"}],
        "end_to_end": [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[CELL]) for m in real["per_layer"]],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
