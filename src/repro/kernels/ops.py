"""Jit'd public wrappers for the Pallas kernels, with platform dispatch.

On TPU these call the Pallas kernels (``flash_attention.py``,
``decode_attention.py``, ``rwkv6_scan.py``, ``mamba2_scan.py``); on any
other backend (CPU dry-runs, tests) they take the pure-jnp oracles in
``ref.py`` — identical semantics, validated by the per-kernel allclose
sweeps in tests/test_kernels.py (which run the Pallas bodies with
``interpret=True``) and compiled for a described v5e by
tests/test_tpu_compile.py. There is no switch to take the oracle on a
TPU: the backend alone decides.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from . import ref
from .decode_attention import (check_latent_page_size, check_page_size,
                               flash_decode, flash_decode_paged,
                               latent_decode_paged, paged_block_rows,
                               prefill_lane_groups)
from .decode_attention import latent_prefill_attention as latent_prefill_kernel
from .decode_attention import paged_prefill_attention as paged_prefill_kernel
from .flash_attention import flash_attention
from .mamba2_scan import mamba2_chunked
from .rwkv6_scan import rwkv6_chunked

__all__ = ["attention", "decode_attention", "paged_decode_attention",
           "latent_decode_attention", "paged_prefill_attention",
           "latent_prefill_attention", "check_page_size",
           "check_latent_page_size", "paged_block_rows",
           "rwkv6_scan", "mamba2_scan",
           "pallas_mode"]


@functools.lru_cache(None)
def pallas_mode() -> str:
    """'tpu' when the default backend is a TPU, else 'off'."""
    return "tpu" if jax.default_backend() == "tpu" else "off"


def attention(q, k, v, *, causal: bool = True,
              sm_scale: Optional[float] = None,
              logits_soft_cap: Optional[float] = None):
    """Flash attention (prefill/training). See ref.attention for semantics."""
    if pallas_mode() == "tpu":
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               logits_soft_cap=logits_soft_cap)
    if q.shape[1] > 1024 or k.shape[1] > 1024:
        # Long sequences: flash-style blocked path so the lowered program
        # has O(S) memory and causal-proportional FLOPs (dry-run realism).
        return ref.attention_blocked(q, k, v, causal=causal,
                                     sm_scale=sm_scale,
                                     logits_soft_cap=logits_soft_cap)
    return ref.attention(q, k, v, causal=causal, sm_scale=sm_scale,
                         logits_soft_cap=logits_soft_cap)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     sm_scale: Optional[float] = None):
    """Flash-decode attention against a KV cache."""
    if pallas_mode() == "tpu":
        return flash_decode(q, k_cache, v_cache, lengths, sm_scale=sm_scale)
    return ref.decode_attention(q, k_cache, v_cache, lengths,
                                sm_scale=sm_scale)


def paged_decode_attention(q, k_pages, v_pages, layer, page_table, lengths,
                           *, sm_scale: Optional[float] = None):
    """Flash-decode of one layer through a page table (continuous-
    batching serving), reading the whole slab ``[L, P, page, K * hd]``.

    See ref.paged_decode_attention for semantics. The Pallas path takes
    one page of all kv heads per block; the layer index and the table
    only change the BlockSpec index map (scalar prefetch)."""
    if pallas_mode() == "tpu":
        return flash_decode_paged(q, k_pages, v_pages, layer, page_table,
                                  lengths, sm_scale=sm_scale)
    return ref.paged_decode_attention(q, k_pages, v_pages, layer,
                                      page_table, lengths, sm_scale=sm_scale)


def latent_decode_attention(q, kv_pages, layer, page_table, lengths, *,
                            sm_scale: float, v_dim: int):
    """Latent (MLA) decode of one layer through a page table, reading the
    whole latent slab ``[L, P, page, C]``: each row is every head's key
    and, in its first ``v_dim`` lanes, value. See
    ref.latent_decode_attention for semantics. The Pallas path copies only
    the slots' live pages (``latent_decode_paged``)."""
    if pallas_mode() == "tpu":
        return latent_decode_paged(q, kv_pages, layer, page_table, lengths,
                                   sm_scale=sm_scale, v_dim=v_dim)
    return ref.latent_decode_attention(q, kv_pages, layer, page_table,
                                       lengths, sm_scale=sm_scale,
                                       v_dim=v_dim)


def paged_prefill_attention(q, k_pages, v_pages, layer, page_table, start,
                            n_valid, *, sm_scale: Optional[float] = None):
    """Chunked prefill attention of one layer for one request through its
    page-table row, the chunk's K/V already in the slab ``[L, P, page,
    K * hd]``. See ref.paged_prefill_attention for semantics. The Pallas
    path (``paged_prefill_attention``) reads only the blocks that hold a
    position below ``start + n_valid``. Pages that are not whole 8-row
    tiles, which its copies cannot slice out of the slab, and heads that
    do not tile 128 lanes (``prefill_lane_groups``) take the oracle's
    dense window."""
    D = q.shape[-1]
    if (pallas_mode() == "tpu" and k_pages.shape[2] % 8 == 0
            and prefill_lane_groups(k_pages.shape[3] // D, D) is not None):
        return paged_prefill_kernel(q, k_pages, v_pages, layer, page_table,
                                    start, n_valid, sm_scale=sm_scale)
    return ref.paged_prefill_attention(q, k_pages, v_pages, layer,
                                       page_table, start, n_valid,
                                       sm_scale=sm_scale)


def latent_prefill_attention(q, kv_pages, layer, page_table, start, n_valid,
                             *, sm_scale: float, v_dim: int):
    """Chunked latent (MLA) prefill attention of one layer for one
    request, the chunk's rows already in the latent slab. See
    ref.latent_prefill_attention for semantics. The Pallas path
    (``latent_prefill_attention``) reads only the live blocks."""
    if pallas_mode() == "tpu":
        return latent_prefill_kernel(q, kv_pages, layer, page_table, start,
                                     n_valid, sm_scale=sm_scale, v_dim=v_dim)
    return ref.latent_prefill_attention(q, kv_pages, layer, page_table,
                                        start, n_valid, sm_scale=sm_scale,
                                        v_dim=v_dim)


def rwkv6_scan(r, k, v, w, u, state=None):
    """RWKV6 WKV recurrence (chunked kernel on TPU)."""
    if pallas_mode() == "tpu":
        return rwkv6_chunked(r, k, v, w, u, state)
    return ref.rwkv6_scan(r, k, v, w, u, state)


def mamba2_scan(x, dt, a, b, c, state=None):
    """Mamba2 SSD recurrence (chunked kernel on TPU)."""
    if pallas_mode() == "tpu":
        return mamba2_chunked(x, dt, a, b, c, state)
    return ref.mamba2_scan(x, dt, a, b, c, state)
