"""A cell's loop and its configuration's deployment are found by file
name: a new deployment lands as new files and ``BENCHMARK.json`` entries,
and the controller loop runs on a second deployment, the paper's testbed,
through those hooks. Both are driven end to end by ``run.main`` on the
CPU, the harness's look for a chip skipped."""
import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

import run
from benchlib import spec
from fault_run import benchmark

SEED = 2**33 + 17

TOY_DEPLOYMENT = '''"""linkedin_tags on machines behind one switch: static scenarios at a
few capacities, the app parallelized anew for each."""
import dataclasses

import numpy as np

from benchlib import deploy, reference


@dataclasses.dataclass
class Scenario:
    name: str
    graph: object
    placement: np.ndarray
    n_machines: int
    cap: float


def corpus(cfg, seed):
    from repro.streams.app import parallelize

    n = int(cfg["n_machines"])
    caps = cfg["capacities_mb_s"]
    out = []
    for k in range(int(cfg["n_scenarios"])):
        g = parallelize(deploy.app(cfg["app"]), seed=seed + k)
        out.append(Scenario(f"tags{k}", g, np.arange(g.n_instances) % n, n,
                            float(caps[k % len(caps)])))
    return out


def program_scenario(sc):
    from repro.net.topology import big_switch
    from repro.streams.scenarios import Scenario as Program

    return Program(sc.name, sc.graph, big_switch(sc.n_machines, sc.cap),
                   sc.placement)


def reference_row(sc, policy, kw, precision="exact"):
    s = reference.testbed_arrays(sc.graph, sc.placement, sc.n_machines,
                                 sc.cap)
    return reference.simulate_ref(s, policy,
                                  int(round(kw["seconds"] / kw["dt"])),
                                  kw["dt"], kw["upd_every"], kw["qcap"],
                                  reference.Arith(precision))
'''

TOY_CONFIG = {"name": "tags-switch-4", "deployment": "tags_switch",
              "app": "linkedin_tags", "n_machines": 4,
              "capacities_mb_s": [1.25, 2.5], "n_scenarios": 4,
              "horizon_s": 20.0, "dt_s": 0.5, "controller_interval_s": 5.0,
              "qcap_mb": 8.0, "chunk_rows": 4}

TOY_TRAFFIC = {"kind": "campaign", "policy": "tcp",
               "limits": {"row_gap_max": 4e-5, "row_gap_median": 2.8e-6}}


@pytest.fixture
def cpu_run(monkeypatch, capsys):
    """``run.main`` in this process on the CPU: no look for a chip, and
    JAX's persistent cache left as the test process has it. Returns the
    result line and the standard error of the run."""
    import jax

    monkeypatch.setattr(run, "setup_jax", lambda root: jax)

    def go(cell, hooks=None, root=spec.ROOT, seconds=1.0):
        capsys.readouterr()
        rc = run.main(["--workload", cell, "--seed", str(SEED),
                       "--seconds", str(seconds)],
                      hooks={"require_chip": False, **(hooks or {})},
                      t_start=time.perf_counter(), root=root)
        out, err = capsys.readouterr()
        assert rc == 0, err[-3000:]
        return json.loads(out.strip().splitlines()[-1]), err
    return go


def _digests(root) -> dict:
    out = {}
    for dirpath, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_deployment_added_as_files_only(tmp_path, cpu_run):
    """A toy deployment (4 scenarios of 20 s of linkedin_tags on a
    4-machine switch under tcp, with its own ``reference_row``) added as a
    deployment module, a configuration, a traffic mix and entries in
    ``BENCHMARK.json`` runs end to end with ``correct`` true, and no file
    that was there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = _digests(root / "bench")

    (root / "bench/deployments/tags_switch.py").write_text(TOY_DEPLOYMENT)
    (root / "bench/configs/tags-switch-4.json").write_text(
        json.dumps(TOY_CONFIG))
    (root / "bench/traffic/campaign-tags-tcp.json").write_text(
        json.dumps(TOY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tags-switch-4", "source": "x",
                             "file": "bench/configs/tags-switch-4.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tags.campaign-tcp",
                               "config": "tags-switch-4",
                               "traffic": "campaign-tags-tcp", "chips": 1,
                               "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "testbed.campaign-tcp" in m.get("workloads", ()):
            m["workloads"].append("tags.campaign-tcp")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    out, err = cpu_run("tags.campaign-tcp", root=str(root), seconds=0.5)
    assert out["correct"] is True, (out["checks"], err[-3000:])
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"campaign_scen_per_s", "setup_s"}
    assert "sampled scenarios" in err
    after = _digests(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/tags-switch-4.json", "deployments/tags_switch.py",
        "traffic/campaign-tags-tcp.json"]


@pytest.mark.parametrize("scaled", [False, True])
def test_testbed_controller_on_cpu(cpu_run, monkeypatch, scaled):
    """``testbed.controller`` cut to 4 flow states: its solves take the
    single-pass per-link path (never the chunked one), every answer
    matches the reference; one state's answer scaled by 1.01 where it is
    produced fails ``rate_gap_max_mb_s``."""
    import jax

    from repro.core import allocator

    passes = []
    single = allocator._per_link_rates

    def spy(*a, **k):
        passes.append("single")
        return single(*a, **k)

    def chunked(*a, **k):
        raise AssertionError("testbed.controller took the chunked solve")

    monkeypatch.setattr(allocator, "_per_link_rates", spy)
    monkeypatch.setattr(allocator, "_per_link_rates_chunked", chunked)
    jax.clear_caches()                      # trace allocate anew
    n_states = 4
    hooks = {"traffic": {"n_states": n_states}}
    if scaled:
        def wrap(solve):
            calls = []

            def run_one(state):
                x = np.array(solve(state))
                if len(calls) % n_states == 0:      # state 0, every cycle
                    x *= 1.01
                calls.append(1)
                return x
            return run_one
        hooks["wrap_solve"] = wrap
    # BENCHMARK.json does not hold the cell (its host latency spreads
    # too widely between processes for the controller bounds); its files
    # are here
    out, err = cpu_run("testbed.controller", dict(hooks,
                                                  benchmark=benchmark()))
    assert passes == ["single"]
    assert "L=16 F=17 nnz(R)=34 states=4" in err
    assert out["attempted"] > n_states and out["failed"] == 0
    gap = out["checks"]["rate_gap_max_mb_s"]
    assert out["correct"] is (not scaled), out["checks"]
    assert (gap["value"] > gap["limit"]) is scaled
