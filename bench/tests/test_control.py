"""The control — the reference computed one precision down (three bfloat16
passes for float32), put in the program's place — comes out not correct
under the cell's own limits, at the cell's own size."""

import numpy as np

import run


def test_control_fails_the_paper_cell():
    files = run.cell_files("paper_n100_eval2")
    system = run.load_module("systems", files["config"]["system"])
    cell = system.Cell(files["config"], files["traffic"], 2**31 + 99)
    cell.free()
    ref = cell.reference()
    ctl = cell.reference(precision="high")
    limits = files["limits"]["limits"]
    sound = system.compare(ref, ref)
    assert all(v <= limits[k] for k, v in sound.items()), sound
    numbers = system.compare(ctl, ref)
    assert np.isfinite(list(numbers.values())).all()
    failed = [k for k, v in numbers.items() if v > limits[k]]
    assert "change_gap" in failed, numbers
