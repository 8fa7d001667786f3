"""Assembles a run's result line from the numbers of its cell."""
from __future__ import annotations

from benchlib import spec
from benchlib.harness import log


def result(cell, args, e2e: dict, ctx: dict, checks: dict, dev: dict,
           attempted: int, failed: int, root: str) -> dict:
    """With ``--trace 0`` the cell's end-to-end metrics; with ``--trace 1``
    its per-layer metrics, each read by its own reader from ``ctx`` (a
    reader that finds nothing to read returns None and the metric is left
    out)."""
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            v = spec.load_reader(m["name"], root)(ctx)
            if v is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    out = {"correct": bool(all(c["ok"] for c in checks.values())
                           and failed == 0),
           "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": dev}
    trace = ctx.get("trace")
    if args.trace and trace is not None:
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    return out
