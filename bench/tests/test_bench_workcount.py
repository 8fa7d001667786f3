"""The work count of one allocate solve comes from the problem alone."""
import numpy as np
import pytest

from benchlib.peaks import peaks
from benchlib.workcount import allocate_work, roofline_s


def _problem(F=40, L=24, seed=0):
    rng = np.random.default_rng(seed)
    R = np.zeros((F, L), np.float32)
    for f in range(F):
        R[f, rng.choice(L, size=3, replace=False)] = 1.0
    kind = rng.integers(0, 3, L).astype(np.int32)
    return R, rng.uniform(1.0, 10.0, L).astype(np.float32), kind


def _count(program, backfill_iters=8):
    R = np.asarray(program.R)
    return allocate_work(int(np.count_nonzero(R)), R.shape[0], R.shape[1],
                         backfill_iters)


def test_count_is_the_same_for_the_sort_and_pallas_solvers():
    import jax.numpy as jnp

    from repro.core.allocator import LinkProgram, allocate
    from repro.core.flowstate import FlowState

    R, cap, kind = _problem()
    prog = LinkProgram(R=jnp.asarray(R), capacity=jnp.asarray(cap),
                       kind=jnp.asarray(kind))
    rng = np.random.default_rng(1)
    st = FlowState(*(jnp.asarray(rng.uniform(0, 10, R.shape[0]),
                                 jnp.float32) for _ in range(5)))
    counts = []
    for solver in ("sort", "pallas"):
        x = allocate(prog, st, dt=5.0, solver=solver)
        assert x.shape == (R.shape[0],)
        counts.append(_count(prog))
    assert counts[0] == counts[1]
    assert counts[0]["ops"] > 0 and counts[0]["bytes"] > 0


def test_count_grows_with_pairs_not_with_the_dense_shape():
    w = allocate_work(1000, 100, 50)
    # ten times the pairs cost more than ten times zero-padding the
    # dense [F, L] shape, which a sparse problem never reads
    assert allocate_work(10_000, 100, 50)["bytes"] > w["bytes"] * 5
    padded = allocate_work(1000, 100, 500)
    assert padded["bytes"] - w["bytes"] == 8 * 450


def test_roofline_and_peaks():
    p = peaks("TPU v5 lite")
    t, bound = roofline_s(allocate_work(7626, 1920, 4096), p)
    assert bound == "bytes" and 0 < t < 1e-6
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
