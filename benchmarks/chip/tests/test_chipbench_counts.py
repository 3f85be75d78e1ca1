"""FLOP and byte counts against hand-worked shapes, and the peak table."""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import pytest  # noqa: E402

import flops as FL  # noqa: E402
from peaks import peaks  # noqa: E402

GRANITE = json.load(open(os.path.join(HERE, "configs",
                                      "granite-3-2b-l4.json")))


def test_matmul_params_of_granite_l4_by_hand():
    d, f, v = 2048, 8192, 49155
    attn = d * 2048 + 2 * d * 512 + 2048 * d      # q, k, v, o
    ffn = 3 * d * f
    assert FL.matmul_params(GRANITE) == 4 * (attn + ffn) + d * v + d


def test_forward_and_train_flops():
    n = FL.matmul_params(GRANITE)
    # one token attending over 10 keys: 4 * layers * heads * d_head * 10
    assert FL.forward_flops(GRANITE, 1, 10) == 2 * n + 4 * 4 * 32 * 64 * 10
    assert FL.train_flops(GRANITE, 3, 6) == 3 * FL.forward_flops(
        GRANITE, 3, 6)
    assert FL.causal_context_sum([3, 1]) == 6 + 1


def test_decode_kernel_cost_by_hand():
    # two slots at 100 and 300 cached tokens: 400 keys in all
    fl, by = FL.decode_kernel_cost(GRANITE, 400, 2)
    assert fl == 4 * 4 * 32 * 64 * 400
    assert by == 2 * 4 * 8 * 64 * 400 * 2 + 2 * 4 * 2 * 32 * 64 * 2
    # grouped queries: about 4 FLOPs per byte, far under the v5e's
    # ridge of 197e12 / 819e9 = 240, so bound by memory bandwidth
    assert 3 < fl / by < 5


def test_fused_loss_cost_by_hand():
    fl, by = FL.fused_loss_cost(GRANITE, 4096)
    assert fl == 6 * 4096 * 2048 * 49155
    assert by == (2 * 4096 * 2048 + 2 * 2048 * 49155) * 2
    assert fl / by > 500       # compute-bound on any chip in the table


def test_peak_table_knows_v5e_and_refuses_the_unknown():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks("cpu")
