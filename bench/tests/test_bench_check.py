"""The comparison that decides ``correct``, on hand-made readings."""

import math

import pytest

import check

REF = {"losses": [10.0, 9.0, 8.0],
       "grad": {"a": 1.0, "b": 2.0, "c": 4.0, "tiny": 1e-6},
       "delta": {"a": 0.1, "b": 0.2, "c": 0.4, "tiny": 5e-3},
       "copy_delta": {"a": 0.1, "b": 0.2, "c": 0.4, "tiny": 0.0}}


def _prog(losses=None, **leaves):
    """The reference's readings as the program's, with ``leaves`` changed."""
    prog = {k: dict(v) for k, v in REF.items() if k != "losses"}
    prog["losses"] = list(REF["losses"] if losses is None else losses)
    prog["tokens_gap"] = 0.0
    for key, value in leaves.items():
        prog[key].update(value)
    return prog


def test_identical_readings_read_nought():
    nums = check.numbers(_prog(), REF)
    assert set(nums) == set(check.NUMBERS)
    assert all(v == 0 for v in nums.values())


def test_leaf_gap_is_over_the_larger_of_leaf_and_median():
    # median of the grad leaves (1e-6, 1, 2, 4) is 1.5
    nums = check.numbers(_prog(grad={"a": 1.3, "tiny": 0.15}), REF)
    assert nums["grad_gap"] == pytest.approx(0.3 / 1.5)
    nums = check.numbers(_prog(grad={"tiny": 0.15}), REF)
    assert nums["grad_gap"] == pytest.approx(0.15 / 1.5, rel=1e-4)


def test_every_step_loss_counts():
    nums = check.numbers(_prog(losses=[10.0, 9.0, 8.25]), REF)
    assert nums["loss_gap"] == pytest.approx(0.25)
    assert check.numbers(_prog(losses=[10.0]), REF)["loss_gap"] == math.inf


def test_leaves_with_nought_gradient_are_left_out_of_the_change():
    # 'tiny' has a reference gradient under 1e-3 of the median leaf's
    nums = check.numbers(_prog(delta={"tiny": 1.0},
                               copy_delta={"tiny": 1.0}), REF)
    assert nums["delta_gap"] == 0 and nums["param_gap"] == 0
    nums = check.numbers(_prog(copy_delta={"b": 0.0}), REF)
    assert nums["param_gap"] == pytest.approx(1.0)


def test_missing_leaves_fail():
    prog = _prog()
    del prog["grad"]["c"]
    assert check.numbers(prog, REF)["grad_gap"] == math.inf


def test_verdict_holds_each_number_to_its_limit(tmp_path):
    limits = {k: 0.1 for k in check.NUMBERS}
    nums = dict.fromkeys(check.NUMBERS, 0.05)
    nums["param_gap"] = 0.2
    v = check.verdict(nums, limits)
    assert [k for k, c in v.items() if not c["ok"]] == ["param_gap"]
    (tmp_path / "cell.json").write_text('{"loss_gap": 1}')
    with pytest.raises(ValueError):
        check.load_limits("cell", tmp_path)
