"""The one traffic generator: the schedule is a fixed trace of the mix,
the seed draws the token ids, every stretch offers its share of the
work, and lengths stay inside their clips."""

import numpy as np
import pytest

from bench import traffic

MIX = {"loop": "open", "arrivals": "poisson", "rate_per_s": 0.7,
       "prompt": {"median": 512, "sigma": 0.8, "min": 32, "max": 1536},
       "output": {"median": 128, "sigma": 0.8, "min": 8, "max": 384},
       "block": 32, "preroll_s": 6.0}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_same_seed_same_tokens(seed):
    assert traffic.prompt_tokens(seed, 5, 40, 151936) == \
        traffic.prompt_tokens(seed, 5, 40, 151936)
    assert traffic.prompt_tokens(seed, 5, 40, 151936) != \
        traffic.prompt_tokens(seed + 1, 5, 40, 151936)


def test_the_schedule_is_the_mix_s_own():
    a = traffic.schedule(MIX, 100)
    assert a == traffic.schedule(dict(MIX), 100)
    b = traffic.schedule(MIX | {"order_seed": 1}, 100)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    for blk in range(3):
        sl = slice(32 * blk, 32 * blk + 32)
        for key in ("prompt_len", "output_len"):
            assert sorted(getattr(r, key) for r in a[sl]) == \
                sorted(getattr(r, key) for r in b[sl])
    # a whole block spans exactly block / rate seconds, in any order
    assert a[32].offset_s == pytest.approx(32 / 0.7)
    assert b[32].offset_s == pytest.approx(32 / 0.7)


def test_the_window_holds_its_share():
    """Pre-roll 20 s and a 51 s window at 0.5 req/s: the window holds
    round(25.5) = 26 requests, the first due as the window opens, the
    same sizes in any order."""
    for order in (0, 5):
        s = traffic.schedule(MIX | {"rate_per_s": 0.5, "order_seed": order},
                             80, 20.0, 51.0)
        win = [r for r in s if 20.0 <= r.offset_s < 71.0]
        assert len(win) == 26 and win[0].offset_s == pytest.approx(20.0)
        assert len([r for r in s if r.offset_s < 20.0]) == 10
        assert sorted(r.prompt_len for r in win) == list(
            traffic.lognormal_quantiles(MIX["prompt"], 26))


def test_large_seeds_do_not_collide():
    a = traffic.prompt_tokens(2**40 + 3, 0, 64, 1000)
    b = traffic.prompt_tokens(3, 0, 64, 1000)
    assert a != b


def test_clipping_and_medians():
    p = traffic.lognormal_quantiles(MIX["prompt"], 1000)
    assert p.min() >= 32 and p.max() == 1536     # the upper tail is clipped
    assert abs(np.median(p) - 512) <= 2
    o = traffic.lognormal_quantiles({"median": 128, "sigma": 2.0, "min": 8,
                                     "max": 256}, 1000)
    assert o.min() == 8 and o.max() == 256        # and here the lower too


def test_gaps_mean_is_the_rate():
    g = traffic.exponential_gaps(2.0, 32)
    assert g.mean() == pytest.approx(0.5)
    assert (np.diff(g) > 0).all()


def test_prompt_tokens_in_vocab():
    t = traffic.prompt_tokens(9, 3, 500, 256)
    assert len(t) == 500 and min(t) >= 0 and max(t) < 256


@pytest.mark.parametrize("bad", [
    {"loop": "sideways"},
    {"loop": "closed", "outstanding": 6},
    {"rate_per_s": 0},
    {"prompt": {"median": 5, "sigma": 1, "min": 10, "max": 20}},
    {"block": 0},
])
def test_validate_refuses(bad):
    with pytest.raises((ValueError, KeyError)):
        traffic.validate(dict(MIX, **bad))
