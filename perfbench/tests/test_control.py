"""The control of ``correct`` at a size a test can hold: the plain reference
in bfloat16, put in the program's place, fails the cells' limits on every
seed; the reference compared with itself passes them."""

import pytest

import control

CELLS = ("knn_bulk_b4096", "classcond_serve_c128")


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 6, 77])
def test_bf16_control_is_not_correct(workload, seed):
    numbers, failed = control.control_numbers(workload, seed, refs=1 << 15)
    assert "dist_gap" in failed, numbers


@pytest.mark.parametrize("workload", CELLS)
def test_reference_against_itself_is_correct(workload):
    numbers, failed = control.control_numbers(workload, 9, refs=1 << 15,
                                              precision="f32")
    assert failed == [], numbers
