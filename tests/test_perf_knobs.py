"""Regression tests for the §Perf hillclimb knobs (EXPERIMENTS.md)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config
from repro.kernels import attention_ref
from repro.models import LM, RuntimeKnobs
from repro.models.attention import flash_attention_xla
from repro.sharding import (batch_shardings, grad_shardings, make_shard_fn,
                            param_shardings)

RNG = np.random.default_rng(11)


def arr(*s):
    return jnp.asarray(RNG.normal(size=s), jnp.float32)


# ------------------------------------------------- H2: causal block skip
@pytest.mark.parametrize("s,q_chunk", [(128, 16), (256, 32), (96, 32)])
def test_causal_skip_matches_ref(s, q_chunk):
    b, h, kv, d = 2, 4, 2, 16
    q, k, v = arr(b, s, h, d), arr(b, s, kv, d), arr(b, s, kv, d)
    out = flash_attention_xla(q, k, v, causal=True, q_chunk=q_chunk,
                              causal_skip=True)
    ref = attention_ref(q.swapaxes(1, 2), k.swapaxes(1, 2),
                        v.swapaxes(1, 2), causal=True).swapaxes(1, 2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_causal_skip_grads_match():
    b, s, h, kv, d = 1, 64, 2, 2, 8
    q, k, v = arr(b, s, h, d), arr(b, s, kv, d), arr(b, s, kv, d)

    def loss(fn_skip):
        def f(q, k, v):
            return jnp.sum(flash_attention_xla(
                q, k, v, causal=True, q_chunk=16,
                causal_skip=fn_skip) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g0, g1 = loss(False), loss(True)
    for a, b_ in zip(g0, g1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-4, rtol=1e-4)


def test_model_with_causal_skip_trains():
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              num_layers=2, vocab_size=64)
    model = LM(cfg, RuntimeKnobs(cache_dtype=jnp.float32, q_chunk=8,
                                 causal_skip=True))
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 32),
                                          0, 64)}
    loss, _ = jax.jit(model.loss)(params, batch)
    assert jnp.isfinite(loss)


# -------------------------------------------------- H3: pure-DP layout
def _mesh():
    return AbstractMesh((16, 16), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


def test_dp_layout_replicates_params_keeps_opt_sharded():
    mesh = _mesh()
    cfg = get_config("internlm2-1.8b")
    model = LM(cfg, RuntimeKnobs(param_dtype=jnp.bfloat16))
    specs = model.param_specs()
    psh = param_shardings(mesh, cfg, specs, fsdp=False, layout="dp")
    for s in jax.tree.leaves(psh):
        assert all(a is None for a in s.spec)
    from repro.sharding import opt_state_shardings

    osh = opt_state_shardings(mesh, cfg, specs, fsdp=False, layout="dp")
    sharded = sum(1 for s in jax.tree.leaves(osh)
                  if any(a is not None for a in s.spec))
    assert sharded > 0  # ZeRO-1 still shards optimizer state


def test_dp_layout_batch_uses_all_axes():
    mesh = _mesh()
    specs = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
    sh = batch_shardings(mesh, specs, layout="dp")["tokens"]
    axes = sh.spec[0]
    assert axes == ("data", "model")


# ------------------------------------- H1: data-only ZeRO-2 grad shardings
def test_grad_shardings_never_use_pod_axis():
    mesh = AbstractMesh((2, 16, 16), ("pod", "data", "model"),
                        axis_types=(AxisType.Auto,) * 3)
    cfg = get_config("qwen3-moe-235b-a22b")
    model = LM(cfg, RuntimeKnobs(param_dtype=jnp.bfloat16))
    specs = model.param_specs()
    gsh = grad_shardings(mesh, cfg, specs)
    for s in jax.tree.leaves(gsh):
        flat = []
        for a in s.spec:
            if isinstance(a, (tuple, list)):
                flat.extend(a)
            elif a is not None:
                flat.append(a)
        assert "pod" not in flat, s.spec


def test_embed_table_never_fsdp_dm_sharded():
    """The H1 fix: FSDP dm-sharding of the embedding triggers per-micro
    replicate-repartition (see EXPERIMENTS.md §Perf H1)."""
    mesh = _mesh()
    for arch in ("qwen3-moe-235b-a22b", "gemma3-27b", "qwen2.5-32b"):
        cfg = get_config(arch)
        model = LM(cfg, RuntimeKnobs(param_dtype=jnp.bfloat16))
        specs = model.param_specs()
        psh = param_shardings(mesh, cfg, specs, fsdp=True)
        spec = psh["embed"]["table"].spec
        assert spec[0] == "model" and spec[1] is None, (arch, spec)
