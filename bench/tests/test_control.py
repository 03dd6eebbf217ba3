"""The control of every cell's check: the plain reference computed in
bfloat16 in the system's place, at a size the CPU holds, must come out not
correct while the system on the same seed comes out correct."""
import pytest

import tiny

CELLS = ["da320.entropic"]


@pytest.mark.parametrize("name", CELLS)
def test_bf16_control_is_not_correct(name):
    sound = tiny.execute(tiny.cell(name), seed=4)
    control = tiny.execute(tiny.cell(name), seed=4, precision="bf16")
    assert sound["correct"]
    assert not control["correct"]
    assert control["checks"]["plan_l1"]["value"] >= 3 * control["checks"]["plan_l1"]["limit"]
