"""The control of each cell at a tiny size on the CPU: the reference
computed in bfloat16, put in the program's place, must come out not
correct under the cell's limits; the float32 reference in its place must
come out correct.  (On the chip the same control runs at the cell's own
size: ``bench/readings.py``.)"""

import jax.numpy as jnp
import pytest

from bench import check, readings, reference
from bench import run as R
from bench.tests import tiny


@pytest.mark.parametrize("name", [w["name"] for w in
                                  R.load_spec()["workloads"]])
@pytest.mark.parametrize("dtype,correct", [(jnp.bfloat16, False),
                                           (jnp.float32, True)])
def test_control(name, dtype, correct):
    _, _, config, mix = tiny.cell(name)
    pts, rec = readings.control_record(config, mix, 2 ** 31 + 5, 1.0, 2)
    area = reference.study_area(pts[:, :2], config["grid_pad"])

    def answer(points, queries):
        return check.reference_for(points, queries, config, area, dtype=dtype)

    ok, table = R.compare(rec, pts, config, mix, area, 2 ** 31 + 5,
                          answer=answer)
    assert ok is correct, table
