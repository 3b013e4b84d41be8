"""The lower-precision control comes out not correct: the reference run in
bfloat16 in the program's place fails the limits a sound run meets."""

from lib import check

import control

from helpers import tiny_cell


def test_bfloat16_control_fails_where_the_program_passes():
    cell = tiny_cell()
    program, ctrl = control.readings(cell, 2**31 + 41, 2.0, require_tpu=False)
    assert check.verdict(program, cell.limits), program
    assert not check.verdict(ctrl, cell.limits), ctrl
    assert ctrl["s_nn_gap"] > 3 * max(program["s_nn_gap"], 1e-7)
