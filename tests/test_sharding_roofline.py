"""Sharding rules (divisibility across all archs) + roofline HLO analyzer."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config, list_archs
from repro.launch.roofline import analyze_hlo, roofline
from repro.models import LM, RuntimeKnobs
from repro.sharding import opt_state_shardings, param_shardings


def _mesh(shape, axes):
    return AbstractMesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh_shape,axes", [
    ((16, 16), ("data", "model")),
    ((2, 16, 16), ("pod", "data", "model")),
])
def test_param_shardings_divisible_all_archs(arch, mesh_shape, axes):
    """Every sharded dim must divide its mesh axes — for the FULL configs."""
    mesh = _mesh(mesh_shape, axes)
    cfg = get_config(arch)
    model = LM(cfg, RuntimeKnobs(param_dtype=jnp.bfloat16))
    specs = model.param_specs()
    for shardings in (param_shardings(mesh, cfg, specs, fsdp=True),
                      param_shardings(mesh, cfg, specs, fsdp=False),
                      opt_state_shardings(mesh, cfg, specs, fsdp=True)):
        flat_sh = jax.tree_util.tree_flatten_with_path(shardings)[0]
        flat_sp = jax.tree.leaves(specs)
        sizes = dict(zip(axes, mesh_shape))
        for (path, sh), spec in zip(flat_sh, flat_sp):
            for dim, ax in zip(spec.shape, sh.spec):
                if ax is None:
                    continue
                n = (sizes[ax] if isinstance(ax, str)
                     else int(jnp.prod(jnp.asarray([sizes[a] for a in ax]))))
                assert dim % n == 0, (arch, path, spec.shape, sh.spec)


def test_big_params_get_meaningfully_sharded():
    """No parameter >100M elements may end up fully replicated (small
    per-layer tensors like MoE routers stay replicated by design)."""
    mesh = _mesh((16, 16), ("data", "model"))
    for arch in list_archs():
        cfg = get_config(arch)
        model = LM(cfg, RuntimeKnobs(param_dtype=jnp.bfloat16))
        specs = model.param_specs()
        sh = param_shardings(mesh, cfg, specs, fsdp=True)
        flat = zip(jax.tree_util.tree_flatten_with_path(specs)[0],
                   jax.tree.leaves(sh))
        for (path, spec), s in flat:
            n = 1
            for d in spec.shape:
                n *= d
            if n > 100_000_000:
                assert any(a is not None for a in s.spec), (arch, path)


# ------------------------------------------------------------ HLO analyzer
_PROBE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import AxisType

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    L, M, K, N = 7, 64, 32, 16

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=L)
        return jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(None, None)))

    x = jax.ShapeDtypeStruct((M, K), jnp.float32)
    w = jax.ShapeDtypeStruct((K, K), jnp.float32)
    low = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                                   NamedSharding(mesh, P(None, None)))).lower(x, w)
    print(low.compile().as_text())
""")


def test_analyze_hlo_trip_count_flops():
    hlo = subprocess.run([sys.executable, "-c", _PROBE],
                         capture_output=True, text=True, timeout=300).stdout
    assert "HloModule" in hlo
    res = analyze_hlo(hlo)
    # 7 scan iterations of (M/2 x K) @ (K x K): 2*32*32*32 per device step
    expected = 7 * 2 * 32 * 32 * 32
    assert res["flops"] == pytest.approx(expected, rel=0.01)


def test_roofline_terms_and_bottleneck():
    coll = {"ici_bytes": 50e9, "dcn_bytes": 0.0}
    t = roofline(197e12, 819e9, coll, n_devices=256)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["ici_s"] == pytest.approx(1.0)
    t2 = roofline(1e12, 819e9 * 3, coll, n_devices=256)
    assert t2["bottleneck"] == "memory"


def test_roofline_dcn_term_per_host():
    coll = {"ici_bytes": 0.0, "dcn_bytes": 12.5e9 / 4}  # per device
    t = roofline(0.0, 0.0, coll, n_devices=512, n_pods=2)
    # per host: 4 chips x (12.5e9/4) bytes = 12.5 GB over 12.5 GB/s = 1 s
    assert t["dcn_s"] == pytest.approx(1.0)
    assert t["bottleneck"] == "collective"
