"""Multi-device campaign sharding (PR 8).

The contract: with the chunk stream sharded across N emulated host
devices (``--xla_force_host_platform_device_count``), campaign metrics
are **bitwise-identical** to the 1-device streamed path and to the
materialized oracle — chunk row quantization is device-count-independent,
so the shard changes *where* a chunk runs, never what it computes. The
corpus size (54) deliberately does not divide the device count (4): the
round-robin stream assignment must handle the ragged tail.

The 4-device half runs in a subprocess because the device count is baked
into XLA at jax import time; the child writes its campaign metrics per
policy to .npy files and the parent (1 stream, ``shard=False``) compares
bitwise. In-child invariants: campaign == unsharded materialized oracle,
repeat call bitwise-stable with a flat compile cache, host staging
bounded by the three rotating slots per stream, and all four devices
actually used. The sharded materialized ``run`` (whole buckets spread over
the devices) is bitwise-equal to the unsharded one.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SECONDS = 8.0
DT = 0.5
N_SCEN = 54          # not divisible by the 4 emulated devices
CHUNK_ROWS = 16      # 27-member buckets -> 2 chunks each -> 4 streams
POLICIES = ("tcp", "appaware", "appfair", "fixed")

_CHILD = r"""
import json, sys
import numpy as np
out_dir = sys.argv[1]
seconds, dt, n_scen, chunk_rows = (float(sys.argv[2]), float(sys.argv[3]),
                                   int(sys.argv[4]), int(sys.argv[5]))
import jax
assert jax.local_device_count() == 4, jax.local_device_count()
from repro.streams import campaign_fleet, compile_fleet
from repro.streams.fleet import FleetRunner

sims = compile_fleet(campaign_fleet(n_scen, seed=0))
xf = [np.full(s.R.shape[0], 0.25, np.float32) for s in sims]
runner = FleetRunner()
info = {}
for policy in %(policies)r:
    kw = dict(x_fixed=xf) if policy == "fixed" else {}
    cr = runner.run_campaign(sims, policy, seconds=seconds, dt=dt,
                             chunk_rows=chunk_rows, **kw)
    st = dict(runner.last_stats)
    # sharded campaign == the unsharded materialized oracle, bitwise
    oracle = np.stack([r.metrics for r in
                       runner.run(sims, policy, seconds=seconds, dt=dt,
                                  shard=False, **kw)])
    np.testing.assert_array_equal(cr.metrics, oracle)
    # repeat is bitwise-stable and compiles nothing new
    n0 = runner.compile_cache_size()
    cr2 = runner.run_campaign(sims, policy, seconds=seconds, dt=dt,
                              chunk_rows=chunk_rows, **kw)
    assert runner.compile_cache_size() == n0
    np.testing.assert_array_equal(cr.metrics, cr2.metrics)
    assert st["peak_staged_rows"] <= 3 * st["chunk_rows"] * st["n_streams"]
    np.save(f"{out_dir}/m4_{policy}.npy", cr.metrics)
    info[policy] = {"n_streams": st["n_streams"],
                    "n_chunks": st["n_chunks"],
                    "transfer_s": st["transfer_s"],
                    "peak_staged_rows": st["peak_staged_rows"],
                    "chunk_rows": st["chunk_rows"]}
# the sharded `run` places whole buckets on devices, so each bucket runs
# the program the unsharded run runs for it: trajectories and metrics are
# compared bitwise here, asserted by the parent
from repro.streams.simulator import metric_index
ulp = {}
for policy in ("tcp", "appaware"):
    sh = runner.run(sims, policy, seconds=seconds, dt=dt, shard=True)
    st_sh = dict(runner.last_stats)
    un = runner.run(sims, policy, seconds=seconds, dt=dt, shard=False)
    traj_equal = all(
        np.array_equal(a.sink_mb, b.sink_mb)
        and np.array_equal(a.link_load, b.link_load)
        and np.array_equal(a.latency, b.latency)
        for a, b in zip(sh, un))
    ms = np.stack([r.metrics for r in sh])
    mu = np.stack([r.metrics for r in un])
    diff_cols = sorted(set(np.nonzero(ms != mu)[1].tolist()))
    max_ulp = int(np.abs(ms.view(np.int32).astype(np.int64)
                         - mu.view(np.int32).astype(np.int64)).max())
    ulp[policy] = {"traj_equal": bool(traj_equal),
                   "diff_cols": [int(c) for c in diff_cols],
                   "max_ulp": max_ulp,
                   "n_buckets": st_sh["n_buckets"],
                   "n_shards": st_sh["n_shards"],
                   "n_dispatches": st_sh["n_dispatches"],
                   "bucket_devices": st_sh["bucket_devices"]}
info["ulp_pin"] = {"sink_col": metric_index("total_sink_mb"),
                   "policies": ulp}
with open(f"{out_dir}/stats.json", "w") as f:
    json.dump(info, f)
print("CHILD_OK")
""" % {"policies": POLICIES}


@pytest.fixture(scope="module")
def four_device_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("m4")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env.setdefault("REPRO_SMOKE", "1")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(repo, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(out), str(SECONDS), str(DT),
         str(N_SCEN), str(CHUNK_ROWS)],
        capture_output=True, text=True, timeout=540, env=env)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "CHILD_OK" in proc.stdout
    with open(out / "stats.json") as f:
        stats = json.load(f)
    return out, stats


class TestShardedCampaignParity:
    def test_bitwise_equal_to_one_device_stream(self, four_device_run):
        out, _ = four_device_run
        from repro.streams import campaign_fleet, compile_fleet
        from repro.streams.fleet import FleetRunner

        sims = compile_fleet(campaign_fleet(N_SCEN, seed=0))
        xf = [np.full(s.R.shape[0], 0.25, np.float32) for s in sims]
        runner = FleetRunner()
        for policy in POLICIES:
            kw = dict(x_fixed=xf) if policy == "fixed" else {}
            # shard=False pins one stream regardless of this process's
            # own device count (the CI 4-device leg runs the whole suite
            # under the XLA flag)
            cr = runner.run_campaign(sims, policy, seconds=SECONDS, dt=DT,
                                     chunk_rows=CHUNK_ROWS, shard=False,
                                     **kw)
            assert runner.last_stats["n_streams"] == 1
            m4 = np.load(out / f"m4_{policy}.npy")
            np.testing.assert_array_equal(cr.metrics, m4)

    def test_all_devices_used(self, four_device_run):
        _, stats = four_device_run
        for policy in POLICIES:
            st = stats[policy]
            # >= 4 chunks stream through (appfair's exact-app buckets
            # chunk differently than tcp's), so all 4 emulated devices
            # get a stream
            assert st["n_streams"] == 4, st
            assert st["n_chunks"] >= 4, st
            assert st["transfer_s"] > 0.0

    def test_staging_bound_holds_when_sharded(self, four_device_run):
        _, stats = four_device_run
        for policy in POLICIES:
            st = stats[policy]
            assert (st["peak_staged_rows"]
                    <= 3 * st["chunk_rows"] * st["n_streams"])

    def test_sharded_run_drift_confined_to_total_sink_mb(
            self, four_device_run):
        """The sharded materialized ``run`` against the unsharded one: the
        buckets spread over the devices (one fused dispatch per device
        used), each bucket whole on one device, so trajectories AND every
        metric column — ``total_sink_mb`` included, which drifted by a
        couple of ULP while buckets were split across devices — are
        bitwise-equal."""
        _, stats = four_device_run
        pin = stats["ulp_pin"]
        for policy, rec in pin["policies"].items():
            assert rec["traj_equal"], policy
            assert rec["diff_cols"] == [] and rec["max_ulp"] == 0, (
                policy, rec)
            n_used = min(4, rec["n_buckets"])
            assert rec["n_shards"] == n_used > 1, (policy, rec)
            assert rec["n_dispatches"] == n_used, (policy, rec)
            assert len(set(rec["bucket_devices"])) == n_used, (policy, rec)
