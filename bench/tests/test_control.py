"""The control and every planted fault fail one of the compared numbers.

The same readings as ``python3 -m bench.control`` takes on the chip at
each cell's size, here on small cells on the CPU: the reference in the
precision below the configuration's (bfloat16, since the small model
computes in float32), and each fault the cell can have, put in the
program's place.  Run by hand: ``python -m pytest bench/tests``.
"""

import jax
import pytest

from bench import control
from bench.tests.small import small_cell

SEED = 2 ** 31 + 17


@pytest.mark.parametrize("workload", ["qwen05b.fedsgd.m3", "engine.n16.m3"])
def test_control_and_faults_fail_a_number(workload):
    readings = control.upper(small_cell(workload), SEED, jax.devices()[:1])
    assert set(readings) == {"control"} | set(
        control.faults_of(small_cell(workload)))
    for name, r in readings.items():
        assert r["fails"], f"{name} passes every limit: {r['numbers']}"
