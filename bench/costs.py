"""Operations and bytes the algorithms need, from shapes, and the peaks.

These are the yardstick's arithmetic: a kernel's roofline share and a
step's share of the chip's peak divide them by device time from the
trace. Counts are of the work the algorithm needs, not of what an
implementation happens to do (padding, copies, masked rows).
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a device not
    in the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def matmul_params(d: dict) -> int:
    """Weights that one token multiplies, unembedding included."""
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    return d["L"] * per_layer + D * d["V"]


def token_flops(d: dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context``
    positions (itself included): 2 per matmul weight, and per layer
    ``4 * H * hd * context`` for the scores and the weighted values."""
    return 2 * matmul_params(d) + d["L"] * 4 * d["H"] * d["hd"] * context


def decode_attn_cost(d: dict, att_lens, rows: int, elem_bytes: int = 2):
    """``(flops, bytes)`` of one call of the paged decode attention
    kernel for one layer: ``rows`` query rows (every slot of the batch)
    of which slot ``b`` attends ``att_lens[b]`` positions. Bytes are the
    K and V of the live positions, q in and the output out."""
    H, K, hd = d["H"], d["K"], d["hd"]
    n = int(sum(att_lens))
    flops = 4 * H * hd * n
    nbytes = elem_bytes * (2 * n * K * hd + 2 * rows * H * hd)
    return flops, nbytes
