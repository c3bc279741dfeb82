"""The control: the plain reference computed with float8 operands (one
precision step below the configurations' bfloat16), put in the
program's place, has to fail a cell's limits.  ``calibrate.py
--control`` reads it at a cell's own size, on the cell's chips; here it
runs at a size a CPU test run can hold."""
import jax
import pytest

import calibrate
import judge
import tiny


@pytest.mark.parametrize("cell", ["mlp-paper.full10-secure",
                                  "mlp-paper.cohort512-secure"])
def test_control_is_not_correct(cell):
    ctx = tiny.ctx(cell)
    got = calibrate.readings(ctx, 2 ** 31 + 41, jax.devices()[:1],
                             program=False, control=True,
                             faults=[])["control"]
    correct, checks = judge.verdict(got, ctx["limits"])
    assert not correct, checks
