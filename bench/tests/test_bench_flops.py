"""Model FLOPs against a hand count."""

import json
from pathlib import Path

import flops
import peaks
import pytest
from configs import qwen3_program

CFG = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
       "head_dim": 2, "intermediate_size": 8, "vocab_size": 10,
       "num_hidden_layers": 3, "program": "qwen3_program"}
QWEN3_4B = Path(__file__).resolve().parents[1] / "configs" / "qwen3-4b.json"


def test_matmul_params_by_hand():
    # per layer: q 4*4 + k,v 2*(4*2) + o 4*4 + MLP 3*4*8 = 16+16+16+96
    assert qwen3_program.matmul_params(CFG) == 3 * 144 + 10 * 4


def test_step_flops_by_hand():
    p = 3 * 144 + 40
    # a document of 4 tokens: 3 targets, causal pairs 3*4/2 = 6;
    # attention 3 (fwd+bwd) * 2 products * 2 FLOPs * pairs * Hq * Dh * L
    want = 6 * p * 3 + 3 * 2 * 2 * 6 * 2 * 2 * 3
    assert flops.step_flops(CFG, [4]) == want
    # a one-token document carries no target and costs nothing
    assert flops.step_flops(CFG, [4, 1]) == want


def test_step_flops_of_the_cell_config_is_pinned():
    """The count of ``qwen3-4b`` on a fixed document list, as the formula
    of ``flops.py`` gave it before the count moved to the family's
    adapter: not a FLOP more or less."""
    cfg = json.loads(QWEN3_4B.read_text())
    docs = [1024, 700, 300, 24, 1, 5000]
    assert flops.step_flops(cfg, docs) == 26274853552128.0
    assert qwen3_program.matmul_params(cfg) == 590807040


def test_head_flops_and_bytes_by_hand():
    # 6 x d x V per target: documents of 4 and 1 tokens carry 3 targets
    assert flops.head_flops(CFG, [4, 1]) == 6 * 4 * 10 * 3
    assert flops.head_flops(CFG, [1]) == 0
    # the head read in forward and backward, its gradient written: bf16
    assert flops.head_bytes(CFG) == 3 * 2 * 4 * 10


def test_peaks_table_refuses_unknown_devices():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")
