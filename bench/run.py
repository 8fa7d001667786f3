#!/usr/bin/env python3
"""Chip benchmark of the SDN bandwidth-allocation system.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: builds
the cell's deployment from ``--seed``, warms every program the cell uses
(set-up), measures for ``--seconds``, then checks what the timed path
produced against the plain numpy reference. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end metrics with ``--trace 0``, per-layer metrics
read from a profiler trace of the window with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and ``checks``, each compared number beside
its limit. It refuses to run, and prints no result, without a TPU, with
fewer chips than the cell asks for, or with ``REPRO_SMOKE`` set.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def setup_jax(root: str):
    """Persistent compilation cache at a fixed path inside the checkout
    (``JAX_COMPILATION_CACHE_DIR`` wins when set), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def main(argv=None, hooks: dict | None = None, t_start: float = T_START,
         root: str = ROOT) -> int:
    hooks = hooks or {}
    args = parse(argv)
    from benchlib import harness, spec

    if os.environ.get("REPRO_SMOKE", "").strip() not in ("", "0"):
        harness.log("bench: REPRO_SMOKE caps the simulated horizon; unset it")
        return 2
    cell = spec.resolve(args.workload, root, hooks.get("benchmark"))
    cell.config.update(hooks.get("config", {}))
    cell.traffic.update(hooks.get("traffic", {}))
    loop = spec.load_loop(cell.traffic["kind"], root)
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (the system under test)

    jax = setup_jax(root)
    devices = jax.devices()
    if hooks.get("require_chip", True):
        if devices[0].platform != "tpu":
            harness.log(f"bench: no TPU (default device is "
                        f"{devices[0].platform}); nothing was run")
            return 1
        if len(devices) < cell.chips:
            harness.log(f"bench: {cell.name} needs {cell.chips} chips, "
                        f"found {len(devices)}; nothing was run")
            return 1
    devices = devices[:cell.chips]
    harness.log(f"bench: {cell.name} seed {args.seed} on "
                f"{devices[0].platform} {devices[0].device_kind} "
                f"x{len(devices)}, jax {jax.__version__}")

    from benchlib import report
    try:
        e2e, ctx, checks, dev, attempted, failed = loop.run(
            cell, args, t_start, devices, hooks)
    except harness.GuardError as e:
        harness.log(f"bench: run refused: {e}")
        return 3
    result = report.result(cell, args, e2e, ctx, checks, dev, attempted,
                           failed, root)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
