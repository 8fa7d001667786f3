"""Device time by named scope and the program's host spans, read from a
trace recorded on a TPU v5e chip and from hand-made operation lists."""
import os

import pytest

from benchlib import scopes, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = os.path.join(DATA, "tiny_tpu.xplane.pb")


@pytest.fixture(scope="module")
def tiny():
    from jax.profiler import ProfileData

    return scopes.read_op_intervals(TINY), ProfileData.from_file(TINY)


def test_op_times_read_as_profile_data_reads_them(tiny):
    chips, pd = tiny
    want = [(ev.start_ns, ev.start_ns + ev.duration_ns)
            for pl in pd.planes if pl.name == "/device:TPU:0"
            for ln in pl.lines if ln.name == tracing.OPS_LINE
            for ev in ln.events]
    assert list(chips) == ["/device:TPU:0"]
    assert [(s, e) for _, s, e in chips["/device:TPU:0"]] == want


def test_while_body_belongs_to_while_and_closed_call(tiny):
    """The trace's loop runs 15 matrix products: each is the fusion
    ``jit(<lambda>)/while/body/closed_call/dot_general`` beside a copy
    named ``jit(<lambda>)/while``, inside a ``%while`` the compiler gave
    no op_name."""
    chips, _ = tiny
    ops = chips["/device:TPU:0"]
    body = [(s, e) for n, s, e in ops
            if n == "jit(<lambda>)/while/body/closed_call/dot_general"]
    copies = [(s, e) for n, s, e in ops if n == "jit(<lambda>)/while"]
    assert len(body) == len(copies) == 15
    assert [n for n, _, _ in ops].count("") == 9     # loops and copies
    dot_s = sum(e - s for s, e in body) * 1e-9
    assert scopes.scope_seconds(chips, "closed_call") == pytest.approx(dot_s)
    assert scopes.scope_seconds(chips, "while") == pytest.approx(
        dot_s + sum(e - s for s, e in copies) * 1e-9)
    assert scopes.scope_seconds(chips, "dot_general") == pytest.approx(dot_s)
    # a prefix of a segment is no scope
    assert scopes.scope_seconds(chips, "whi") == 0.0
    # within the window the reduction reads, no scope holds more than the
    # chip's busy time
    _, pd = tiny
    steps = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for pl in pd.planes if pl.name == tracing.HOST_PLANE
             for ln in pl.lines for ev in ln.events if ev.name == "bench.step"]
    lo, hi = min(s for s, _ in steps), max(e for _, e in steps)
    busy = tracing.reduce(TINY)["busy_s"]
    assert 0 < scopes.scope_seconds(chips, "jit(<lambda>)", lo, hi) <= busy


def test_scoped_loop_and_its_body_count_once():
    loop = "jit(allocate)/per_link/while"
    chips = {
        "/device:TPU:0": [(loop, 0.0, 100.0),
                          (loop + "/body/dot_general", 10.0, 30.0),
                          (loop + "/body/add", 20.0, 50.0),
                          ("jit(allocate)/backfill/while", 120.0, 130.0),
                          ("", 0.0, 200.0)],
        "/device:TPU:1": [(loop + "/body/add", 5.0, 15.0)],
    }
    assert scopes.scope_seconds(chips, "per_link") == pytest.approx(110e-9)
    assert scopes.scope_seconds(chips, "backfill") == pytest.approx(10e-9)
    # clipped to the window
    assert scopes.scope_seconds(chips, "per_link", 25.0, 125.0) == (
        pytest.approx(75e-9))
    assert scopes.scope_seconds(chips, "backfill", 25.0, 125.0) == (
        pytest.approx(5e-9))
    assert scopes.scope_seconds(chips, "tick") == 0.0


def test_host_spans(tiny):
    _, pd = tiny
    spans = [ev for pl in pd.planes if pl.name == tracing.HOST_PLANE
             for ln in pl.lines for ev in ln.events
             if ev.name == "bench.step"]
    secs, n = scopes.host_span_seconds(pd, "bench.step")
    assert n == len(spans) == 3
    assert secs == pytest.approx(sum(ev.duration_ns for ev in spans) * 1e-9)
    lo = spans[0].start_ns + 1000.0
    secs_lo, n_lo = scopes.host_span_seconds(pd, "bench.step", lo=lo)
    assert n_lo == 3 and secs_lo == pytest.approx(secs - 1e-6)
    assert scopes.host_span_seconds(pd, "campaign.startup") == (0.0, 0)


def test_trace_reduction_reads_as_before():
    """Every key of the reduction the accepted metrics read, pinned on the
    recorded trace. The ``%while`` and the operations of its body are
    both in ``device_ops``: that list counts a loop's body twice."""
    t = tracing.reduce(TINY)
    assert t["window_s"] == 0.015146779
    assert t["busy_s"] == 9.587e-06
    assert t["n_chips"] == 1
    assert t["modules_s"] == {"jit__lambda": 9.622e-06}
    assert t["modules_n"] == {"jit__lambda": 5}
    assert [(n.split(" ")[0], v) for n, v in t["device_ops"]] == [
        ("%multiply_add_fusion", 3.916e-06), ("%while", 3.4330000000000004e-06),
        ("%convolution_tanh_fusion.2", 2.8630000000000004e-06),
        ("%copy.9", 1.373e-06), ("%reduce_sum.7", 8.19e-07),
        ("%copy.11", 4.54e-07), ("%convert.1", 4.6e-08)]
    assert t["idle_gaps"] == [
        ["$time sleep", 0.0036000180000000004], ["$time sleep", 0.003026705],
        ["$time sleep", 0.003018387], ["$time sleep", 0.002427273],
        ["$time sleep", 0.001739932], ["$time sleep", 0.001324869],
        ["ReadSyncFlag", 2e-09], ["ReadSyncFlag", 2e-09],
        ["ReadSyncFlag", 1e-09], ["PJRT_LoadedExecutable_Execute", 1e-09]]
