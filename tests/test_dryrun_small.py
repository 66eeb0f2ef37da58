"""Small-mesh dry-run: the full lower+compile pipeline on 8 host devices
(subprocess isolates the XLA device-count flag). The production 512-chip
sweep lives in experiments/dryrun; this keeps the pipeline covered by CI.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest


def _run(code: str) -> dict:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch,kind", [
    ("qwen3_1p7b", "train"), ("mamba2_780m", "decode"),
    ("granite_moe_3b_a800m", "train"),
])
def test_small_mesh_lower_compile(arch, kind):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, dataclasses, jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro import configs
        from repro.models import family
        from repro.optim import AdamWConfig, adamw
        from repro.launch.shardings import make_rules
        from repro.launch.train import (abstract_params, abstract_opt_state,
                                        batch_spec_tree, make_train_step,
                                        tree_shardings)
        from repro.launch.serve import abstract_cache, make_decode_step
        from repro.launch import roofline
        from repro.configs.base import input_specs

        cfg = configs.smoke("{arch}")
        cfg = dataclasses.replace(cfg, microbatches=2)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = make_rules(mesh)
        fam = family(cfg)
        opt_cfg = AdamWConfig()
        with mesh:
            if "{kind}" == "train":
                ap = abstract_params(cfg)
                ao = abstract_opt_state(cfg, opt_cfg)
                ps = fam.param_specs(cfg, rules)
                p_sh = tree_shardings(mesh, ap, ps, rules)
                o_sh = tree_shardings(mesh, ao, adamw.state_specs(ps), rules)
                batch = {{
                  "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                  "targets": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                  "mask": jax.ShapeDtypeStruct((8, 64), jnp.bfloat16)}}
                b_sh = tree_shardings(mesh, batch, batch_spec_tree(batch),
                                      rules)
                fn = jax.jit(make_train_step(cfg, rules, opt_cfg),
                             in_shardings=(p_sh, o_sh, b_sh, None),
                             out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
                comp = fn.lower(ap, ao, batch,
                                jax.ShapeDtypeStruct((), jnp.int32)).compile()
            else:
                ap = abstract_params(cfg)
                ps = fam.param_specs(cfg, rules)
                p_sh = tree_shardings(mesh, ap, ps, rules)
                cache = abstract_cache(cfg, 8, 128)
                c_sh = tree_shardings(mesh, cache,
                                      fam.cache_specs(cfg, rules), rules)
                fn = jax.jit(make_decode_step(cfg, rules),
                             in_shardings=(p_sh, c_sh, None, None),
                             out_shardings=(None, c_sh),
                             donate_argnums=(1,))
                comp = fn.lower(ap, cache,
                                jax.ShapeDtypeStruct((8, 1), jnp.int32),
                                jax.ShapeDtypeStruct((8,), jnp.int32)
                                ).compile()
            rf = roofline.analyze(comp, chips=8, model_flops=1.0)
            mem = comp.memory_analysis()
        print(json.dumps({{
            "flops": rf.flops, "bytes": rf.hbm_bytes,
            "coll": rf.coll_bytes,
            "temp": mem.temp_size_in_bytes}}))
    """)
    out = _run(code)
    assert out["flops"] > 0
    assert out["bytes"] > 0


def test_dryrun_skip_rule():
    # dryrun sets XLA_FLAGS at import (required for its own __main__ use);
    # snapshot env so the pytest process and its children stay at 1 device
    before = os.environ.get("XLA_FLAGS")
    try:
        from repro import configs
        from repro.launch import dryrun
        assert dryrun.skip_reason(configs.get("qwen3-8b"), "long_500k")
        assert dryrun.skip_reason(configs.get("mamba2-780m"),
                                  "long_500k") is None
        assert dryrun.skip_reason(configs.get("qwen3-8b"),
                                  "train_4k") is None
    finally:
        if before is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = before
