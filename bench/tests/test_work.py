"""Work counted from logical shapes, and the peaks table."""
import math

import pytest

from bench import work

PSQ = {"n_bits_a": 4, "n_bits_w": 4, "n_bits_sf": 4, "xbar_rows": 128}
V5E = work.peaks("TPU v5 lite")


def test_one_psq_layer_by_hand():
    # mistral-7b's gate projection at 32 decode rows
    rows, k, o = 32, 4096, 14336
    ops, nbytes = work.psq_call(rows, k, o, PSQ)
    assert ops == 2 * 32 * 4096 * 14336 * 4 * 4
    codes = 4096 * 14336 * 4 / 8                  # 4-bit codes at rest
    sf = 32 * 4 * 4 * 14336 * 4 / 8                # 32 tiles x 16 pairs
    assert nbytes == codes + sf + 32 * 4096 * 4 / 8 + 32 * 14336 * 4
    least = work.psq_min_seconds(1, rows, [("gate", k, o)], PSQ, V5E)
    assert least == pytest.approx(max(ops / 393e12, nbytes / 819e9))
    # sixteen binary planes make 32 rows bound by the int8 peak ...
    assert least == pytest.approx(ops / 393e12)


def test_a_single_row_is_bound_by_the_weight_bytes():
    ops, nbytes = work.psq_call(1, 4096, 14336, PSQ)
    assert ops / 393e12 < nbytes / 819e9


def test_roofline_of_an_exact_kernel_cannot_pass_100():
    layers = [("wq", 4096, 4096), ("down", 14336, 4096)]
    least = work.psq_min_seconds(8, 32, layers, PSQ, V5E)
    calls = (work.psq_call(32, k, o, PSQ) for _, k, o in layers)
    per_call = [max(a / 393e12, b / 819e9) for a, b in calls]
    assert least == pytest.approx(8 * sum(per_call))


def test_model_flops():
    cfg = {"num_hidden_layers": 2, "hidden_size": 8, "vocab_size": 10,
           "num_attention_heads": 2, "head_dim": 4}
    layers = [("a", 8, 8), ("b", 8, 16)]
    assert work.params_per_token(cfg, layers) == 2 * (64 + 128) + 80
    assert work.model_flops(cfg, layers, 3, 12) == pytest.approx(
        2 * 464 * 3 + 4 * 2 * 2 * 4 * 12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    assert math.isclose(V5E["bf16_flops_per_s"], 197e12)
