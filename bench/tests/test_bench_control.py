"""The low-precision control fails the comparison: the plain reference in
the program's place, computed with fp8 products, against the reference,
at the tiny cell's size on the CPU; so do the faults planted in the
reference. The same readings at each
cell's own size are made on the chip by ``bench/calibrate.py``."""

import calibrate
import check
import tiny


def test_control_fails_and_program_passes():
    recs = []
    cell = tiny.cell()
    calibrate.readings(cell, [11, 12], {11, 12}, recs.append,
                       require_tpu=False)
    for r in recs:
        ok = all(v["ok"] for v in check.verdict(
            r["numbers"], cell.limits).values())
        assert ok is (r["kind"] == "program"), r
    assert {r["kind"] for r in recs} == {"program", "control", "half_batch",
                                        "unrefreshed"}
