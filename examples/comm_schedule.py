import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
"""Cross-layer collective scheduling demo (DESIGN.md §2): compile a train
step on a small mesh, extract its collective flows from the HLO, and run the
paper's allocator over them to produce the issue order / chunking plan.

    PYTHONPATH=src python examples/comm_schedule.py --arch qwen1.5-0.5b
"""
import argparse

import jax
import numpy as np

from repro.compile_cache import setup_compile_cache
from repro.core.scheduler import extract_flows, plan_schedule
from repro.launch.mesh import _mk
from repro.launch.shardings import batch_shardings, opt_shardings, param_shardings
from repro.models.registry import ShapeSpec, get_config, get_model
from repro.sharding.policy import sharding_policy
from repro.train.optim import AdamW
from repro.train.step import make_train_step


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced(d_model=256, n_layers=2, vocab=1024)
    api = get_model(cfg)
    mesh = _mk((4, 2), ("data", "model"))
    spec = ShapeSpec("demo", 256, 8, "train")
    with sharding_policy(mesh):
        opt = AdamW(lr=1e-3)
        step = make_train_step(api, opt)
        params_ab = api.abstract_params()
        opt_ab = jax.eval_shape(opt.init, params_ab)
        p_sh = param_shardings(mesh, api)
        ispecs = api.input_specs(spec)
        shardings = (p_sh, opt_shardings(mesh, p_sh, opt_ab),
                     batch_shardings(mesh, ispecs))
        compiled = jax.jit(step, in_shardings=shardings).lower(
            params_ab, opt_ab, ispecs).compile()

    hlo = compiled.as_text()
    mesh_axes = {a: mesh.shape[a] for a in mesh.axis_names}
    flows = extract_flows(hlo, mesh_axes)
    print(f"extracted {len(flows)} collective flows from the compiled step")
    by_axis = {}
    for f in flows:
        by_axis.setdefault(f.axis, []).append(f)
    for axis, fs in by_axis.items():
        mb = sum(f.bytes for f in fs) / 1e6
        print(f"  axis {axis:6s}: {len(fs):3d} flows, {mb:8.1f} MB/step")

    compute_s = float(compiled.cost_analysis().get("flops", 1e9)) / 197e12
    sched = plan_schedule(flows, mesh_axes, step_compute_s=max(compute_s, 1e-3))
    print(f"allocator schedule: total comm {sched.est_total_comm_s * 1e3:.2f} ms, "
          f"exposed (not overlapped) {sched.est_exposed_s * 1e3:.2f} ms")
    print("top-5 most urgent flows (paper's min-max transfer-time order):")
    for i in sched.order[:5]:
        f = flows[i]
        print(f"  {f.kind:18s} axis={f.axis:6s} {f.bytes / 1e6:8.2f} MB "
              f"rate={sched.rates[i] / 1e9:6.2f} GB/s chunks={sched.chunks[i]}")


if __name__ == "__main__":
    main()
