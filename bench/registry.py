"""Find a cell's parts by name, from ``BENCHMARK.json`` and files of their own.

Under the benchmark's directory (``paths[0]`` of ``BENCHMARK.json``):

    configs/<config>.json       sizes as run, engine sizes, source, cuts
    traffic/<traffic>.json      the mix's parameters (``bench/traffic.py``)
    metrics/<metric>.py         one metric, end-to-end or per-layer:
                                ``read(run)`` gives its value or None
    families/<model_type>.py    seeded weights and the program's view
    reference/<model_type>.py   the plain float32 reference
    limits/<workload>.json      the limits that decide ``correct``

A new configuration, mix, metric or cell is new files and new entries in
``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

from . import traffic as traffic_mod


def _module(path: Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    name = "bench_" + tag + "_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    def __init__(self, root):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.spec["paths"][0]

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        cfg = json.loads((self.root / entry["file"]).read_text())
        cfg["name"] = name
        return cfg

    def mix(self, name: str) -> dict:
        return traffic_mod.load(self.dir / "traffic" / f"{name}.json")

    def limits(self, workload: str) -> dict:
        return json.loads(
            (self.dir / "limits" / f"{workload}.json").read_text())

    def family(self, model_type: str):
        return _module(self.dir / "families" / f"{model_type}.py", "family")

    def reference(self, model_type: str):
        return _module(self.dir / "reference" / f"{model_type}.py", "ref")

    def _applies(self, metric: dict, workload: str) -> bool:
        if "workloads" in metric:
            return workload in metric["workloads"]
        return True

    def _with_reader(self, m: dict) -> dict:
        mod = _module(self.dir / "metrics" / f"{m['name']}.py", "metric")
        return dict(m, read=mod.read)

    def end_to_end(self, workload: str) -> list:
        """This cell's end-to-end metrics, each with its ``read``."""
        return [self._with_reader(m) for m in self.spec["end_to_end"]
                if self._applies(m, workload)]

    def per_layer(self, workload: str) -> list:
        """This cell's per-layer metrics, each with its ``read``. One that
        moves an end-to-end metric the cell does not report is an error."""
        e2e = {m["name"] for m in self.spec["end_to_end"]
               if self._applies(m, workload)}
        out = []
        for m in self.spec["per_layer"]:
            if not self._applies(m, workload):
                continue
            if m["moves"] not in e2e:
                raise ValueError(
                    f"per-layer metric {m['name']!r} moves {m['moves']!r}, "
                    f"which cell {workload!r} does not report")
            out.append(self._with_reader(m))
        return out
