"""The KV store of a benchmark run, in a process of its own.

    python bench/kvproc.py

Starts a ``KVServer`` (TCP plus the same-host carriers), prints its
endpoint urls as one JSON line on standard output, and serves until its
standard input closes. It never imports JAX.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core.kvserver import KVServer  # noqa: E402


def main() -> None:
    server = KVServer().start()
    print(json.dumps(server.endpoints), flush=True)
    sys.stdin.read()           # the parent closes the pipe when it is done
    server.stop()


if __name__ == "__main__":
    main()
