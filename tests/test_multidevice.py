"""Multi-device behaviors, each in a subprocess with 8 host devices (the
main pytest process must keep seeing 1 device — see conftest).

Covers: sharded train-step lowering+compile on a 2x4 mesh (a miniature of
the production dry-run), elastic checkpoint restore onto a different mesh
shape, and the roofline analyzer on a genuinely partitioned module.
"""
import subprocess
import sys
import textwrap

import pytest

HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import LM, RuntimeKnobs
from repro.optim import AdamWConfig
from repro.runtime.steps import init_train_state, make_train_step, train_state_specs
from repro.sharding import batch_shardings, cache_shardings, make_shard_fn, opt_state_shardings, param_shardings

def tiny_model(mesh=None):
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              num_layers=2, vocab_size=64, d_model=64,
                              num_heads=4, num_kv_heads=2, head_dim=16,
                              d_ff=128)
    knobs = RuntimeKnobs(cache_dtype=jnp.float32, q_chunk=16)
    if mesh is not None:
        knobs = knobs.with_(shard_fn=make_shard_fn(mesh, cfg))
    return LM(cfg, knobs)
"""


def run_sub(body: str, timeout=560):
    code = HEADER + textwrap.dedent(body)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, cwd=".")
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    out = run_sub("""
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        model = tiny_model(mesh)
        cfg = model.cfg
        state = init_train_state(model, jax.random.PRNGKey(0))
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                              (4, 32), 0, 64)}
        # single-device reference
        ref_model = tiny_model()
        step0 = jax.jit(make_train_step(ref_model, AdamWConfig()))
        ref_state, ref_metrics = step0(init_train_state(
            ref_model, jax.random.PRNGKey(0)), batch)

        specs = train_state_specs(model)
        p_sh = param_shardings(mesh, cfg, specs["params"], fsdp=False)
        o_sh = opt_state_shardings(mesh, cfg, specs["params"], fsdp=False)
        state_sh = {"params": p_sh, "opt": {"master": o_sh, "mu": o_sh,
                    "nu": o_sh, "step": NamedSharding(mesh, P())}}
        b_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch))
        step = jax.jit(make_train_step(model, AdamWConfig()),
                       in_shardings=(state_sh, b_sh),
                       out_shardings=(state_sh, None))
        with mesh:
            state = jax.device_put(state, state_sh)
            new_state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert abs(float(metrics["loss"]) - float(ref_metrics["loss"])) < 1e-3, \\
            (float(metrics["loss"]), float(ref_metrics["loss"]))
        print("OK", float(metrics["loss"]))
    """)
    assert "OK" in out


def test_elastic_checkpoint_restore_across_mesh_shapes():
    out = run_sub("""
        from repro.checkpoint import restore, save_checkpoint
        mesh_a = jax.make_mesh((2, 4), ("data", "model"),
                               axis_types=(AxisType.Auto,) * 2)
        mesh_b = jax.make_mesh((4, 2), ("data", "model"),
                               axis_types=(AxisType.Auto,) * 2)
        model = tiny_model(mesh_a)
        cfg = model.cfg
        specs = train_state_specs(model)
        sh_a = param_shardings(mesh_a, cfg, specs["params"], fsdp=True)
        sh_b = param_shardings(mesh_b, cfg, specs["params"], fsdp=True)
        state = init_train_state(model, jax.random.PRNGKey(0))
        params_a = jax.device_put(state["params"], sh_a)
        save_checkpoint("/tmp/elastic_ck", 3, params_a)
        restored, meta = restore("/tmp/elastic_ck", specs["params"], sh_b)
        assert meta["step"] == 3
        flat0 = jax.tree.leaves(state["params"])
        flat1 = jax.tree.leaves(restored)
        for a, b in zip(flat0, flat1):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored leaves live on the NEW mesh's sharding
        for leaf, sh in zip(flat1, jax.tree.leaves(sh_b)):
            assert leaf.sharding == sh
        print("OK elastic")
    """)
    assert "OK elastic" in out


def test_mini_dryrun_with_serve_step_and_roofline():
    out = run_sub("""
        from repro.launch.roofline import analyze_hlo, roofline
        from repro.runtime.steps import make_serve_step
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        model = tiny_model(mesh)
        cfg = model.cfg
        pspecs = model.param_specs()
        p_sh = param_shardings(mesh, cfg, pspecs, fsdp=False)
        c_specs = model.cache_specs(8, 64)
        c_sh = cache_shardings(mesh, c_specs)
        b = {"tokens": jax.ShapeDtypeStruct((8, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        b_sh = batch_shardings(mesh, b)
        step = make_serve_step(model)
        with mesh:
            low = jax.jit(step, in_shardings=(p_sh, c_sh, b_sh["tokens"],
                                              b_sh["pos"]),
                          out_shardings=(None, c_sh)).lower(
                pspecs, c_specs, b["tokens"], b["pos"])
            comp = low.compile()
        res = analyze_hlo(comp.as_text())
        assert res["flops"] > 0
        terms = roofline(res["flops"], res["hbm_bytes"], res, n_devices=8)
        assert terms["step_s"] > 0
        ma = comp.memory_analysis()
        assert ma.temp_size_in_bytes >= 0
        print("OK dryrun", res["flops"], terms["bottleneck"])
    """)
    assert "OK dryrun" in out
