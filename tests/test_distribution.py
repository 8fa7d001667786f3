"""Distribution tests: sharding policy resolution, HLO collective parsing,
and dry-run-lite — an 8-device (subprocess) lower+compile of train/prefill/
decode on a 2x4 mesh for representative families."""
import json
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch import hlo_stats
from repro.launch.mesh import _mk
from repro.sharding import policy as pol


class TestPolicy:
    def _mesh(self):
        return _mk((1, 1), ("data", "model"))

    def test_spec_resolution_and_dedup(self):
        with pol.sharding_policy(self._mesh()):
            spec = pol.spec_for("batch", "seq", "heads", None)
            # batch -> ("pod","data") filtered to ("data",); heads -> model
            assert spec[0] in ("data", ("data",))
            assert spec[2] == "model"
            # duplicate mesh axis is dropped for later logical axes
            spec2 = pol.spec_for("kv_seq", "kv_heads")
            assert spec2[0] == "model" and spec2[1] is None

    def test_missing_mesh_axes_dropped(self):
        with pol.sharding_policy(self._mesh()):
            # "pod" doesn't exist on a single-pod mesh
            spec = pol.spec_for("batch")
            assert spec[0] in ("data", ("data",))

    def test_noop_outside_context(self):
        import jax.numpy as jnp
        x = jnp.ones((4, 4))
        assert pol.shard_as(x, "batch", "embed") is x
        assert pol.shard_count("batch") == 1

    def test_divisibility_guard(self):
        mesh = _mk((1, 1), ("data", "model"))
        sh = pol.param_sharding(mesh, ("vocab", "embed"), (7, 8))
        # vocab=7 not divisible by model-size 1? size-1 always divides; spec kept
        assert sh.spec[1] is not None or sh.spec[0] is not None


class TestHloStats:
    HLO = textwrap.dedent("""\
      %all-reduce.1 = f32[16,512]{1,0} all-reduce(%x), channel_id=1, replica_groups=[16,16]<=[256], to_apply=%add
      %ag = bf16[64,1024]{1,0} all-gather(%y), channel_id=2, replica_groups=[8,32]<=[256], dimensions={0}
      %rs = f32[4,256]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[2,4]<=[8], to_apply=%add
      %cp = u8[1000]{0} collective-permute(%w), channel_id=4, source_target_pairs={{0,1}}
      %ar2 = (f32[8]{0}, f32[8]{0}) all-reduce(%a, %b), channel_id=5, replica_groups=[4,4]<=[16], to_apply=%add
      %notacoll = f32[2,2]{1,0} add(%p, %q)
    """)

    def test_parse(self):
        st = hlo_stats.collective_stats(self.HLO)
        assert st["count"] == 5
        assert st["all-reduce"] == 16 * 512 * 4 + 2 * 8 * 4
        # all-gather operand = result / group size (32)
        assert st["all-gather"] == 64 * 1024 * 2 // 32
        # reduce-scatter operand = result * group size (4)
        assert st["reduce-scatter"] == 4 * 256 * 4 * 4
        assert st["collective-permute"] == 1000
        assert st["total"] == sum(
            st[k] for k in ("all-reduce", "all-gather", "reduce-scatter",
                            "collective-permute"))

    def test_ignores_done(self):
        txt = ("%s = f32[8]{0} all-reduce-start(%x), replica_groups=[2,2]<=[4]\n"
               "%d = f32[8]{0} all-reduce-done(%s)\n")
        st = hlo_stats.collective_stats(txt)
        assert st["count"] == 1


_SUBPROC = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    from repro.launch.mesh import _mk
    from repro.launch.shardings import (batch_shardings, opt_shardings,
                                        param_shardings, cache_shardings,
                                        replicated)
    from repro.models.registry import ShapeSpec, get_config, get_model
    from repro.sharding.policy import sharding_policy
    from repro.train.optim import AdamW
    from repro.train.step import make_train_step
    from repro.launch import hlo_stats

    arch = {arch!r}
    cfg = get_config(arch).reduced(d_model=128, vocab=1024,
                                   n_heads=8, n_kv_heads=8, head_dim=None)
    api = get_model(cfg)
    mesh = _mk((2, 4), ("data", "model"))
    out = {{}}
    with sharding_policy(mesh):
        # train
        spec = ShapeSpec("t", 256, 8, "train")
        opt = AdamW(lr=1e-3)
        step = make_train_step(api, opt)
        pab = api.abstract_params()
        oab = jax.eval_shape(opt.init, pab)
        psh = param_shardings(mesh, api)
        isp = api.input_specs(spec)
        c = jax.jit(step, in_shardings=(psh, opt_shardings(mesh, psh, oab),
                                        batch_shardings(mesh, isp))
                    ).lower(pab, oab, isp).compile()
        st = hlo_stats.collective_stats(c.as_text())
        out["train_collectives"] = st["count"]
        ca = c.cost_analysis()
        out["train_flops"] = float(ca.get("flops", 0))
        # decode
        dspec = ShapeSpec("d", 64, 8, "decode")
        cab = jax.eval_shape(lambda: api.init_cache(8, 64))
        csh = cache_shardings(mesh, cab)
        dfn = lambda p, cache, t, pos: api.decode(p, cache, t, pos)
        c2 = jax.jit(dfn, in_shardings=(
            psh, csh,
            batch_shardings(mesh, {{"tokens": api.input_specs(dspec)["tokens"]}})["tokens"],
            replicated(mesh))).lower(
            pab, cab, api.input_specs(dspec)["tokens"],
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
        out["decode_ok"] = True
    print(json.dumps(out))
""")


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "dbrx-132b", "mamba2-370m"])
def test_dryrun_lite_8dev(arch):
    """Compile a reduced config on a faked 8-device 2x4 mesh in a subprocess
    (device count must be set before jax initializes)."""
    code = _SUBPROC.format(arch=arch)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root",
                            # host-platform device faking is a CPU feature;
                            # never probe for TPUs from the bare subprocess
                            "JAX_PLATFORMS": "cpu"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["train_collectives"] > 0, "SPMD produced no collectives?"
    assert out["train_flops"] > 0
    assert out["decode_ok"]
