"""Resolve a benchmark cell by name to its files.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* configuration ``<c>``: the ``file`` of its entry (``bench/configs/<c>.json``);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, a module with
  ``read(ctx) -> float | None``;
* the loop of traffic kind ``<k>`` (the traffic's ``kind``):
  ``bench/loops/<k>.py``, a module with ``run(cell, args, t_start,
  devices, hooks)``, ``control(cell, seed)`` and ``LAYERS``, the layers
  its per-layer metrics may name;
* the deployment ``<d>`` (the configuration's ``deployment``):
  ``bench/deployments/<d>.py``, a module with what its loops call of it
  (each loop's docstring lists that).

Adding any of them is adding a file; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
import types

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]     # metric entries the cell reports, in order
    per_layer: list[dict]
    deployment: types.ModuleType   # bench/deployments/<config's deployment>


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def traffic_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "traffic", f"{name}.json")


def metric_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "metrics", f"{name}.py")


def loop_path(kind: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "loops", f"{kind}.py")


def deployment_path(name: str, root: str = ROOT) -> str:
    return os.path.join(root, "bench", "deployments", f"{name}.py")


def load_file(path: str, what: str) -> types.ModuleType:
    """The module in file ``path`` (``what`` it is, for the error a
    missing file raises), executed afresh under a name made of its
    directory and file name, and registered, so that its dataclasses
    resolve their annotations."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{what} has no file {path}")
    stem = os.path.splitext(os.path.basename(path))[0]
    modname = re.sub(r"\W", "_", "bench_" + os.path.basename(
        os.path.dirname(path)) + "_" + stem)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: str = ROOT, bench: dict | None = None
            ) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(traffic_path(w["traffic"], root)) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        deployment=load_deployment(config["deployment"], root))


def load_reader(name: str, root: str = ROOT):
    """The ``read`` function of per-layer metric ``name``."""
    return load_file(metric_path(name, root),
                     f"per-layer metric {name!r}").read


def load_loop(kind: str, root: str = ROOT) -> types.ModuleType:
    """The loop module of traffic kind ``kind``."""
    return load_file(loop_path(kind, root), f"traffic kind {kind!r}")


def load_deployment(name: str, root: str = ROOT) -> types.ModuleType:
    """The deployment module ``name`` of a configuration."""
    return load_file(deployment_path(name, root), f"deployment {name!r}")
