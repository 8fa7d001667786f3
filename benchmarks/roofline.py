"""Roofline terms per (arch × shape × mesh) from the dry-run artifacts
(deliverable g). Reads results/dryrun/*.json; prints one row per cell."""
from __future__ import annotations

import json
import pathlib

from benchmarks.common import emit
from repro.compile_cache import setup_compile_cache
from repro.launch.roofline import cell_terms

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "results" / "dryrun"


def terms(rec: dict) -> dict:
    t = cell_terms(rec)
    return {
        "compute_s": t["t_c"], "memory_floor_s": t["t_mf"],
        "memory_hlo_s": t["t_m"], "collective_s": t["t_n"],
        "dominant": t["dominant"], "model_flops": t["model_flops"],
        "useful_ratio": t["ratio"],
        "roofline_fraction": t["frac"],
        "step_lower_bound_s": t["bound"],
    }


def run() -> list[dict]:
    rows = []
    for f in sorted(RESULTS.glob("*.json")):
        rec = json.loads(f.read_text())
        if not rec.get("ok"):
            rows.append({"name": f"roofline_{f.stem}",
                         "error": rec.get("error", "?")[:80]})
            continue
        t = terms(rec)
        rows.append({
            "name": f"roofline_{rec['arch']}_{rec['shape']}_{rec['mesh']}",
            "us_per_call": t["step_lower_bound_s"] * 1e6,
            "compute_s": f"{t['compute_s']:.4f}",
            "memory_floor_s": f"{t['memory_floor_s']:.4f}",
            "memory_hlo_s": f"{t['memory_hlo_s']:.4f}",
            "collective_s": f"{t['collective_s']:.4f}",
            "dominant": t["dominant"],
            "useful_ratio": f"{t['useful_ratio']:.3f}",
            "roofline_fraction": f"{t['roofline_fraction']:.3f}",
        })
    return rows


def main() -> None:
    emit(run(), "roofline")


if __name__ == "__main__":
    setup_compile_cache()
    main()
