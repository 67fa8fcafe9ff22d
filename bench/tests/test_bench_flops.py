"""Model FLOPs against a hand count."""

import flops
import peaks
import pytest

CFG = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 2, "intermediate_size": 8, "vocab_size": 10,
       "num_hidden_layers": 3}


def test_matmul_params_by_hand():
    # per layer: q 4*4 + k,v 2*(4*2) + o 4*4 + MLP 3*4*8 = 16+16+16+96
    assert flops.matmul_params(CFG) == 3 * 144 + 10 * 4


def test_step_flops_by_hand():
    p = 3 * 144 + 40
    # a document of 4 tokens: 3 targets, causal pairs 3*4/2 = 6;
    # attention 3 (fwd+bwd) * 2 products * 2 FLOPs * pairs * Hq * Dh * L
    want = 6 * p * 3 + 3 * 2 * 2 * 6 * 2 * 2 * 3
    assert flops.step_flops(CFG, [4]) == want
    # a one-token document carries no target and costs nothing
    assert flops.step_flops(CFG, [4, 1]) == want


def test_peaks_table_refuses_unknown_devices():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")
