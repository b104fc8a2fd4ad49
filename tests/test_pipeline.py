"""Pipeline parallelism: pipelined forward == sequential forward (subprocess
with 4 host devices as 4 stages)."""
import subprocess
import sys
import textwrap

PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType
    from repro.sharding.pipeline import make_pipelined_forward

    S, LPS, M, MB, D = 4, 2, 6, 3, 8   # 4 stages x 2 layers, 6 microbatches
    rng = np.random.default_rng(0)
    # per-layer MLP params stacked (stages, layers_per_stage, ...)
    w = jnp.asarray(rng.normal(size=(S, LPS, D, D)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(S, LPS, D)) * 0.1, jnp.float32)
    params = {"w": w, "b": b}
    x = jnp.asarray(rng.normal(size=(M, MB, D)), jnp.float32)

    def stage_fn(p, h):
        def layer(h, wb):
            wi, bi = wb
            return jnp.tanh(h @ wi + bi), None
        h, _ = jax.lax.scan(layer, h, (p["w"], p["b"]))
        return h

    # sequential reference: all S*LPS layers in order
    def reference(x):
        h = x
        for s in range(S):
            h = stage_fn({"w": w[s], "b": b[s]}, h)
        return h

    mesh = jax.make_mesh((S,), ("stage",),
                         axis_types=(AxisType.Explicit,))
    # leading dim S is sharded over the stage axis; shard_map's local view
    # keeps it as a singleton that pipeline_apply's p[0] strips
    fwd = make_pipelined_forward(stage_fn, mesh, axis_name="stage")
    out = jax.jit(fwd)(params, x)
    ref = jax.vmap(reference)(x)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, err
    # the pipelined HLO must contain collective-permute (the PP schedule)
    txt = jax.jit(fwd).lower(params, x).compile().as_text()
    assert "collective-permute" in txt
    print("OK pipeline", err)
""")


def test_pipeline_matches_sequential():
    p = subprocess.run([sys.executable, "-c", PROG], capture_output=True,
                       text=True, timeout=560, cwd=".")
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    assert "OK pipeline" in p.stdout
