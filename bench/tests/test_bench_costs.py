"""FLOP and byte counts against hand counts, and the peaks table."""

import pytest

from bench import costs

# qwen1.5-0.5b and qwen1.5-4b, as in bench/configs
Q05 = dict(D=1024, H=16, K=16, hd=64, F=2816, L=24, V=151936, tied=True)
Q4 = dict(D=2560, H=20, K=20, hd=128, F=6912, L=40, V=151936, tied=False)


def test_matmul_params_by_hand():
    # per layer: q,k,v,o 4 * 1024 * 1024 + mlp 3 * 1024 * 2816
    per = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert costs.matmul_params(Q05) == 24 * per + 1024 * 151936
    per = 4 * 2560 * 2560 + 3 * 2560 * 6912
    assert costs.matmul_params(Q4) == 40 * per + 2560 * 151936


def test_token_flops_by_hand():
    # 2 per weight, plus per layer 4 * H * hd per attended position
    assert costs.token_flops(Q05, 1) - costs.token_flops(Q05, 0) == \
        24 * 4 * 16 * 64
    assert costs.token_flops(Q4, 1000) == \
        2 * costs.matmul_params(Q4) + 40 * 4 * 20 * 128 * 1000


@pytest.mark.parametrize("d,lens,rows,flops,nbytes", [
    # 2 slots live at 10 and 5 positions of 4 rows, hd 64, 16 kv heads:
    # K and V 2 * 15 * 16 * 64 elements, q and out 2 * 4 * 16 * 64
    (Q05, [10, 5, 0, 0], 4, 4 * 16 * 64 * 15,
     2 * (2 * 15 * 16 * 64 + 2 * 4 * 16 * 64)),
    (Q4, [1024] * 6, 6, 4 * 20 * 128 * 6144,
     2 * (2 * 6144 * 20 * 128 + 2 * 6 * 20 * 128)),
])
def test_decode_attn_cost_by_hand(d, lens, rows, flops, nbytes):
    assert costs.decode_attn_cost(d, lens, rows) == (flops, nbytes)


def test_peaks_known_and_unknown():
    pk = costs.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9 imaginary")
