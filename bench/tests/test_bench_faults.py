"""The check fails a run whose timed path is broken underneath: each fault
is planted in the engine of a tiny CPU run, and ``correct`` must come out
false. The faults are those a served cell can have: a token altered
where it is produced, a decode step that leaves the cache unchanged,
half of the batch left out, and replies delivered to the wrong request.
(One chip: there is no exchange between chips to leave out.)"""

import time

import jax.numpy as jnp
import pytest

from bench.harness import run_cell
from bench.tests import tiny


def token_altered(engine):
    fn, V = engine._decode, engine.model.cfg.vocab_size

    def broken(*a):
        toks, pages = fn(*a)
        return (toks + 1) % V, pages
    broken._cache_size = fn._cache_size
    engine._decode = broken


def state_unchanged(engine):
    fn = engine._decode

    def broken(params, pages, *a):
        toks, _ = fn(params, pages, *a)
        return toks, pages
    broken._cache_size = fn._cache_size
    engine._decode = broken


def half_batch(engine):
    fn = engine._decode

    def broken(params, pages, tokens, tables, lengths, mask):
        mask = mask & (jnp.arange(mask.shape[0]) % 2 == 0)
        return fn(params, pages, tokens, tables, lengths, mask)
    broken._cache_size = fn._cache_size
    engine._decode = broken


def misdelivered(engine):
    fn, held = engine._finish, []

    def broken(req, result, slot):
        held.append((req, result, slot))
        if len(held) == 2:
            (ra, xa, sa), (rb, xb, sb) = held
            fn(ra, xb, sa)
            fn(rb, xa, sb)
            held.clear()
    engine._finish = broken


@pytest.mark.parametrize("fault,caught_by", [
    (token_altered, "max_logit_gap"), (state_unchanged, "max_logit_gap"),
    (half_batch, "max_logit_gap"), (misdelivered, "wrong_replies")])
def test_planted_fault_is_not_correct(tmp_path, fault, caught_by):
    root = tiny.make_root(tmp_path)
    res = run_cell(root, tiny.CELL, 2**32 + 5, 2.0, False, time.time(),
                   require_tpu=False, engine_hook=fault, compile_cache=False)
    assert not res["correct"], res["checks"]
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"], res["checks"]
