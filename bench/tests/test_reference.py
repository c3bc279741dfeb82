"""The plain reference against the program at a small size on the CPU,
where the program's float32 products are exact float32: both cells'
configurations, through the harness's own program and replay, and a mix
that differs from a cell's in its data alone (plain aggregation)."""
import jax
import pytest

import calibrate
import tiny

# What secure aggregation's 2^-20 fixed-point grid alone leaves between
# the program and the reference at these sizes (measured 3e-5, 5e-6 and
# 7e-4 for the MLP), with a factor of about ten for other seeds.
CLOSE = {"loss_gap": 1e-3, "step_gap": 1e-3, "step_diff": 1e-2}


@pytest.mark.parametrize("cell,traffic", [
    ("mlp-paper.cohort512-secure", {}),
    ("mlp-paper.full10-secure", {}),
    ("mlp-paper.full10-secure", {"aggregation": {"kind": "plain"}}),
])
def test_program_matches_reference(cell, traffic):
    got = calibrate.readings(tiny.ctx(cell, **traffic), 2 ** 31 + 77,
                             jax.devices()[:1], program=True, control=False,
                             faults=[])["program"]
    for name, bound in CLOSE.items():
        assert got[name] < bound, (name, got)
