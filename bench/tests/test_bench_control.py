"""The control fails the limits of each cell: the plain reference, put in
the program's place and computed one precision step below the
configuration's (float32 elementwise, contractions in three bf16 passes,
the TPU's ``high``), reads above the limit the cell holds the program to,
at the cell's own sample and sizes (``bench/control.py``)."""
import pytest

import control
from benchlib import spec

SEED = 4_000_000_007
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]
         if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell):
    c = spec.resolve(cell)
    fn = {"campaign": control.campaign_control,
          "controller": control.controller_control}[c.traffic["kind"]]
    checks = fn(c, SEED)
    assert not all(ch["ok"] for ch in checks.values()), checks
