"""Benchmark runner — one section per paper table/figure. Prints
``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import traceback

from repro.compile_cache import setup_compile_cache


def main() -> None:
    from benchmarks import (
        fairness,
        latency,
        motivation,
        overhead,
        throughput,
        utilization,
    )

    sections = [
        ("fig3", motivation.main),
        ("fig8+9", throughput.main),
        ("fig10+11", latency.main),
        ("fig12", utilization.main),
        ("fig13", fairness.main),
        ("overhead", overhead.main),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    failures = 0
    for name, fn in sections:
        if only and only not in name:
            continue
        print(f"# --- {name} ---")
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    setup_compile_cache()
    main()
