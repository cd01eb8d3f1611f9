"""Operations and bytes of the latent (MLA) paged decode kernel, from shapes.

``latent_decode_paged`` (``src/repro/kernels/decode_attention.py``)
serves one layer of latent attention for a batch: each live position's
latent row, ``C = kv_lora_rank + qk_rope_head_dim`` lanes (576 at
Moonlight's widths), is read once as the key of every head and, in its
first ``kv_lora_rank`` lanes, as the value. Counts are of the work the
algorithm needs, as in ``bench/costs.py``: the row's ``C`` lanes (not
the tile padding it is stored in), the absorbed queries in and the
latent outputs out of every slot of the batch.
"""

from __future__ import annotations


def latent_decode_cost(d: dict, att_lens, rows: int, elem_bytes: int = 2):
    """``(flops, bytes)`` of one call for one layer: ``rows`` slots of
    which slot ``b`` attends ``att_lens[b]`` positions. Per position and
    head ``2 * C`` FLOPs for the score and ``2 * R`` for the value; per
    position ``C`` elements read; per slot ``H * C`` in and ``H * R``
    out."""
    H, C, R = d["H"], d["C"], d["R"]
    n = int(sum(att_lens))
    flops = 2 * H * (C + R) * n
    nbytes = elem_bytes * (n * C + rows * H * (C + R))
    return flops, nbytes
