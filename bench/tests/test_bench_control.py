"""The control fails the limits of each cell: the plain reference, put in
the program's place and computed one precision step below the
configuration's (float32 elementwise, contractions in three bf16 passes,
the TPU's ``high``), reads above the limit the cell holds the program to,
at the cell's own sample and sizes (``control`` of the cell's loop, which
``bench/control.py`` runs); the cells of BENCHMARK.json and the
candidates whose files are here (``fault_run.CANDIDATES``)."""
import pytest

from benchlib import spec
from fault_run import benchmark

SEED = 4_000_000_007
BENCH = benchmark()
CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_a_limit(cell):
    c = spec.resolve(cell, bench=BENCH)
    checks = spec.load_loop(c.traffic["kind"]).control(c, SEED)
    assert not all(ch["ok"] for ch in checks.values()), checks
