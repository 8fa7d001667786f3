"""Every cell of BENCHMARK.json, and every candidate cell whose files are
here (``fault_run.CANDIDATES``), resolves by name to its files, the file
keeps to the shapes its format allows, and a configuration, traffic mix or
per-layer metric added as a new file is found without editing any file
that is there."""
import json
import os
import re
import shutil

import pytest

from benchlib import spec
from fault_run import benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_benchmark()


def _loop_layers(cell: str) -> tuple:
    """The layers the loop of ``cell``'s traffic kind declares."""
    return spec.load_loop(spec.resolve(cell).traffic["kind"]).LAYERS


WITH_CANDIDATES = benchmark()


@pytest.mark.parametrize("cell",
                         [w["name"] for w in WITH_CANDIDATES["workloads"]])
def test_cell_resolves(cell):
    c = spec.resolve(cell, bench=WITH_CANDIDATES)
    assert c.chips in (1, 4)
    loop = spec.load_loop(c.traffic["kind"])
    assert callable(loop.run) and callable(loop.control)
    assert "limits" in c.traffic
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    moved = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        assert m["moves"] in moved
        assert m["layer"] in loop.LAYERS, (m["name"], m["layer"])
        assert callable(spec.load_reader(m["name"]))


def test_benchmark_file_shapes():
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(BENCH) == keys
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            assert e["name"] not in names
            names.add(e["name"])
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.exists(
            os.path.join(spec.ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = [w["name"] for w in BENCH["workloads"]]
    layers = {c: _loop_layers(c) for c in cells}
    for m in BENCH["per_layer"]:
        for c in m.get("workloads", cells):
            assert m["layer"] in layers[c], (m["name"], m["layer"], c)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_new_files_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    (root / "bench/configs/storm-testbed-16.json").write_text(json.dumps(
        dict(json.load(open(root / "bench/configs/storm-testbed-8.json")),
             name="storm-testbed-16")))
    (root / "bench/traffic/campaign-appfair.json").write_text(json.dumps(
        {"kind": "campaign", "policy": "appfair",
         "limits": {"row_gap_max": 1.0}}))
    (root / "bench/metrics/pipeline.rows_per_chunk.py").write_text(
        "def read(ctx):\n    return 64.0\n")
    bench["configs"].append({"name": "storm-testbed-16", "source": "x",
                             "file": "bench/configs/storm-testbed-16.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "testbed16.campaign-appfair",
                               "config": "storm-testbed-16",
                               "traffic": "campaign-appfair", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "pipeline.rows_per_chunk",
                               "unit": "rows", "better": "higher",
                               "source": "program_counter",
                               "layer": "campaign pipeline",
                               "moves": "campaign_scen_per_s",
                               "workloads": ["testbed16.campaign-appfair"]})
    bench["end_to_end"][0]["workloads"].append("testbed16.campaign-appfair")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.resolve("testbed16.campaign-appfair", str(root))
    assert c.config["name"] == "storm-testbed-16"
    assert c.traffic["policy"] == "appfair"
    assert [m["name"] for m in c.per_layer] == ["pipeline.rows_per_chunk"]
    assert spec.load_reader("pipeline.rows_per_chunk", str(root))({}) == 64.0
    # the cells that were there resolve as before
    assert spec.resolve("testbed.campaign-tcp", str(root)).traffic == \
        spec.resolve("testbed.campaign-tcp").traffic


@pytest.mark.parametrize("load", [spec.load_loop, spec.load_deployment])
def test_missing_file_is_named(tmp_path, load):
    """A traffic kind or deployment with no file of its own is an error
    that names the file it looked for."""
    with pytest.raises(FileNotFoundError, match="no_such_thing.py"):
        load("no_such_thing", str(tmp_path))
