"""The trace reduction on small traces with known answers."""
import os

import pytest

from benchlib import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _profile(name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, name)) as f:
        return ProfileData.from_text_proto(
            "".join(ln for ln in f if not ln.lstrip().startswith("#")))


def test_two_chips():
    t = tracing.reduce_profile(_profile("two_chips.pbtxt"))
    assert t["n_chips"] == 2
    assert t["window_s"] == pytest.approx(10e-6)
    # chip 0: 4000 + 2000 ns busy, chip 1: 1000 ns; averaged
    assert t["busy_s"] == pytest.approx((6000 + 1000) / 2 * 1e-9)
    secs, runs = tracing.module_seconds(t, r"^jit_allocate$")
    assert runs == 2 and secs == pytest.approx(6000e-9)
    assert t["device_ops"][0][0] == "fusion.1"
    assert t["device_ops"][0][1] == pytest.approx((2000 + 2000 + 1000) / 2
                                                   * 1e-9)
    name, gap = t["idle_gaps"][0]
    assert name == "PjitFunction(allocate)" and gap == pytest.approx(4000e-9)


def test_no_device_plane_is_an_error():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto('planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError):
        tracing.reduce_profile(pd)


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e chip: three host spans ``bench.step``
    around a jitted loop of matrix products and a small jitted add."""
    t = tracing.reduce(os.path.join(DATA, "tiny_tpu.xplane.pb"))
    assert t["n_chips"] == 1
    assert 0 < t["busy_s"] < t["window_s"] < 1.0
    assert t["device_ops"] and all(s > 0 for _, s in t["device_ops"])
    assert sum(t["modules_n"].values()) >= 3
    assert t["idle_gaps"] and all(g > 0 for _, g in t["idle_gaps"])
