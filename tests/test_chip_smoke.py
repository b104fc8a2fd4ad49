"""``chip_smoke.py`` and the launcher's platform choices, off the chip.

The script must refuse a machine without a TPU before building anything,
and its correctness checks must pass on a served model and fail when a
kernel is wrong.  The checks run here on a tiny model with the launcher's
TPU knobs (bf16, Pallas kernels in interpret mode).
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys
import textwrap
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve as launch
from repro.models import LM
from repro.runtime.serve import ServeConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
# tiny-model bounds: its logits have std ~0.16 (d_model 64); bf16 rounding
# moves them by max ~0.005 / rms ~0.0007, a decode mask one position short
# by max ~0.019 / rms ~0.0044
BOUND, RMS_BOUND = 0.01, 0.002


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_a_machine_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout and "model:" not in p.stdout
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("backend,sharded,want", [
    ("cpu", False, (jnp.float32, jnp.float32, False)),
    ("cpu", True, (jnp.float32, jnp.float32, False)),
    ("tpu", False, (jnp.bfloat16, jnp.bfloat16, True)),
    ("tpu", True, (jnp.bfloat16, jnp.bfloat16, False)),
])
def test_serving_knobs_follow_the_platform(backend, sharded, want):
    k = launch.serving_knobs(backend, sharded=sharded)
    assert (k.compute_dtype, k.cache_dtype, k.use_pallas) == want
    assert k.param_dtype == want[0]


def test_compile_cache_dir_env_wins_else_fixed_path(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, val: calls.append((name, val)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert launch.enable_compile_cache(tmp_path) == str(tmp_path / "env")
    assert calls == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = launch.enable_compile_cache(tmp_path)
    assert path == str(tmp_path / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert launch.enable_compile_cache() == str(ROOT / ".jax_cache")


@pytest.mark.parametrize("backend,changes,want", [
    ("tpu", {}, 77),
    ("cpu", {}, None),
    ("tpu", {"num_pages": 9}, 9),
    ("tpu", {"cache": "dense"}, None),
    ("tpu", {"mesh_shape": (1, 2)}, None),
])
def test_launcher_fits_the_pool_only_for_one_paged_tpu_engine(
        monkeypatch, backend, changes, want):
    asked = []

    def fake_fit(model, device, **shape):
        asked.append(shape)
        return types.SimpleNamespace(num_pages=77)

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(launch, "fit_device_pool", fake_fit)
    cfg = get_config("internlm2-1.8b", smoke=True)
    serve_cfg = dataclasses.replace(
        ServeConfig(batch_slots=4, max_len=128, page_size=16,
                    prefill_chunk=32, cache="paged"), **changes)
    model = LM(cfg, launch.serving_knobs(backend, sharded=False))
    assert launch.fitted_num_pages(model, serve_cfg) == want
    assert asked == ([dict(slots=4, max_len=128, page_size=16, chunk=32)]
                     if want == 77 else [])


def test_sharded_params_are_created_in_the_engine_layout():
    """With a mesh, ``build_serving_model`` draws the params straight into
    ``serve_param_shardings``: the same values as unsharded, and no device
    holds more than its share."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys
        sys.path.insert(0, "src")
        import jax
        import numpy as np
        from repro.configs import get_config
        from repro.launch.mesh import make_serve_mesh
        from repro.launch.serve import build_serving_model
        from repro.sharding import serve_param_shardings

        cfg = get_config("internlm2-1.8b", smoke=True)
        model, params = build_serving_model(cfg, mesh_shape=(1, 2))
        _, plain = build_serving_model(cfg)
        want = serve_param_shardings(make_serve_mesh((1, 2)), cfg,
                                     model.param_specs())
        held = {}
        for a, b, s in zip(jax.tree.leaves(params), jax.tree.leaves(plain),
                           jax.tree.leaves(want)):
            assert a.sharding.is_equivalent_to(s, a.ndim), (a.sharding, s)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for shard in a.addressable_shards:
                held[shard.device.id] = (held.get(shard.device.id, 0)
                                         + shard.data.nbytes)
        assert sorted(held) == [0, 1], held
        assert held[0] == held[1] < sum(a.nbytes for a in
                                        jax.tree.leaves(plain)), held
        assert any(not a.sharding.is_fully_replicated
                   for a in jax.tree.leaves(params))
        print("OK")
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 0 and "OK" in p.stdout, p.stderr


@pytest.fixture(scope="module")
def served():
    """A tiny model served by the paged engine with the TPU knobs."""
    smoke = _chip_smoke()
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              num_layers=2)
    model = LM(cfg, launch.serving_knobs("tpu", sharded=False))
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, ServeConfig(
        batch_slots=4, max_len=128, page_size=16, prefill_chunk=32,
        cache="paged"))
    requests = smoke.make_requests(cfg.vocab_size, n=3, max_new=5,
                                   lens=(20, 60))
    outputs, _ = smoke.serve(engine, requests)
    shape = dict(slots=4, num_pages=engine.kv.pool.num_pages, max_len=128,
                 page_size=16, chunk=32)
    prompts = [r.prompt for r in requests]
    ref = smoke.reference_logits(cfg, params, prompts, outputs)
    return smoke, engine, model, params, prompts, outputs, ref, shape


def test_smoke_checks_pass_on_the_served_model(served):
    smoke, engine, model, params, prompts, outputs, ref, shape = served
    assert all(len(o) == 5 for o in outputs)
    assert "pool drained" in smoke.check_drained(engine)
    assert smoke.check_tokens(ref, outputs, BOUND) <= BOUND
    for splits in (0, 2):
        got = smoke.replay_logits(model, params, prompts, outputs,
                                  splits=splits, **shape)
        smoke.check_logits(f"split-K {splits}", got, ref, outputs, BOUND,
                           RMS_BOUND)


def test_smoke_checks_catch_a_wrong_token(served):
    smoke, engine, _, _, prompts, outputs, ref, _ = served
    wrong = [list(o) for o in outputs]
    wrong[1][2] = int(ref[1, 2].argmin())
    with pytest.raises(AssertionError, match="below the reference"):
        smoke.check_tokens(ref, wrong, BOUND)
    with pytest.raises(AssertionError, match=r"give back \d+/15 served"):
        smoke.check_served_program(engine, prompts, wrong)


def test_served_program_replay_gives_back_the_served_tokens(served):
    smoke, engine, _, _, prompts, outputs, _, _ = served
    assert "15/15 served tokens bitwise" in smoke.check_served_program(
        engine, prompts, outputs)


def test_replay_table_puts_requests_on_the_highest_pages():
    smoke = _chip_smoke()
    table = smoke.replay_table(3, slots=4, num_pages=40, max_len=128,
                               page_size=16)
    assert table.shape == (4, 8)
    assert sorted(table[:3].ravel()) == list(range(16, 40))
    assert not table[3].any()
    assert (np.diff(table[:3].ravel()) != 1).any()  # shuffled
    with pytest.raises(AssertionError, match="need more"):
        smoke.replay_table(3, slots=4, num_pages=24, max_len=128,
                           page_size=16)


def test_smoke_checks_catch_a_wrong_decode_mask(served, monkeypatch):
    """A paged decode kernel that attends one position too few fails the
    logits check."""
    import repro.kernels as kernels

    smoke, _, model, params, prompts, outputs, ref, shape = served
    real = kernels.paged_decode_attention
    monkeypatch.setattr(kernels, "paged_decode_attention",
                        lambda q, k, v, t, pos, **kw: real(q, k, v, t,
                                                           pos - 1, **kw))
    got = smoke.replay_logits(model, params, prompts, outputs, **shape)
    with pytest.raises(AssertionError, match="off the reference"):
        smoke.check_logits("mutant", got, ref, outputs, BOUND, RMS_BOUND)


def test_smoke_check_drained_flags_a_held_page(served):
    smoke, engine, *_ = served
    page = engine.kv.pool.alloc(1)[0]
    try:
        with pytest.raises(AssertionError, match="pages in use"):
            smoke.check_drained(engine)
    finally:
        engine.kv.pool.decref(page)
    np.testing.assert_equal(engine.kv.page_table, 0)


@pytest.mark.parametrize("budget,pages", [(1e12, 33), (1e5, None)])
def test_pool_fit_caps_at_dense_equivalent_or_refuses(served, budget, pages):
    """A budget above every footprint gets the dense-equivalent pool; one
    below a single slot's chain is refused."""
    from repro.launch.pool_fit import fit_pool_pages

    _, _, model, *_ = served
    shape = dict(slots=4, max_len=128, page_size=16, chunk=32)
    if pages is None:
        with pytest.raises(ValueError, match="no pool"):
            fit_pool_pages(model, budget=budget, **shape)
        return
    fit = fit_pool_pages(model, budget=budget, **shape)
    assert fit.num_pages == pages
    assert all(fp.peak <= budget for fp in fit.footprints)
