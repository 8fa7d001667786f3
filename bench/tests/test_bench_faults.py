"""A run with its timed path broken underneath reports ``correct`` false:
each cell is driven on the CPU at a small size by ``fault_run.py`` (the
harness's look for a chip skipped, all else as on the chip), once sound
and once for every fault the cell can have (``faults/<kind>.py``); the
cells of ``BENCHMARK.json`` and the candidates whose files are here."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

from benchlib import spec  # noqa: E402
from fault_run import benchmark, faults  # noqa: E402


def _cases():
    out = []
    bench = benchmark()
    for w in bench["workloads"]:
        c = spec.resolve(w["name"], bench=bench)
        out.append((w["name"], "none", True))
        out += [(w["name"], f, False)
                for f in faults(c.traffic["kind"]).FAULTS]
    return out


CASES = _cases()


def _run(cell: str, fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("REPRO_SMOKE", None)
    p = subprocess.run([sys.executable, os.path.join(HERE, "fault_run.py"),
                        cell, fault], cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,fault,sound", CASES)
def test_fault_is_caught(cell, fault, sound):
    out = _run(cell, fault)
    assert out["correct"] is sound, out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["metrics"]


def test_no_tpu_means_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("REPRO_SMOKE", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fattree.controller", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    env["REPRO_SMOKE"] = "1"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "fattree.controller", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_alone_runs_nothing(tmp_path):
    import shutil
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "testbed.campaign-tcp", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
