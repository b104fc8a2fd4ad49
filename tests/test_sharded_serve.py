"""Sharded serving: a ServeEngine split over a device mesh must produce
BITWISE-identical token streams to the single-device engine.

Each test runs in a subprocess with 8 forced host devices (the main
pytest process must keep seeing 1 device — see conftest), the same
pattern as tests/test_multidevice.py.  The equality tests mix greedy and
seeded-sampled requests: sampled trajectories only match when every
logit is bit-exact, so integer token equality is the strongest check we
can state.  The sharding layout under test is the gather-form TP of
``sharding/rules.py`` (``ServeShardFn`` / ``serve_param_shardings`` /
``serve_cache_shardings``) — reductions stay in single-device order, so
identity holds by construction, and these tests pin that construction.
"""
import subprocess
import sys
import textwrap

HEADER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import sys
sys.path.insert(0, "src")
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import LM, RuntimeKnobs
from repro.runtime.serve import (Request, SamplingParams, ServeConfig,
                                 ServeEngine)

def tiny_model():
    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              num_layers=2, vocab_size=64, d_model=64,
                              num_heads=4, num_kv_heads=2, head_dim=16,
                              d_ff=128)
    return LM(cfg, RuntimeKnobs(cache_dtype=jnp.float32, q_chunk=16))

def requests(n=6, max_new=12):
    rng = np.random.default_rng(7)
    out = []
    for i in range(n):
        p = rng.integers(1, 64, size=int(rng.integers(3, 20)))
        sp = (SamplingParams() if i % 2 == 0 else
              SamplingParams(temperature=0.8, top_k=20, seed=i))
        out.append(Request(req_id=i, prompt=p.astype(np.int32),
                           max_new_tokens=max_new, sampling=sp))
    return out

def run_engine(**cfg_kw):
    m = tiny_model()
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, params, ServeConfig(batch_slots=4, max_len=64,
                                             **cfg_kw))
    for r in requests():
        eng.submit(r)
    done = eng.run(max_ticks=500)
    return {r.req_id: (tuple(r.output), r.finish_reason)
            for r in done}, eng
"""


def run_sub(body: str, timeout=560):
    code = HEADER + textwrap.dedent(body)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, cwd=".")
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_sharded_dense_decode_bitwise_identical():
    """TP-only (1,2) and TP x data (2,2) dense engines reproduce the
    unsharded engine's greedy AND seeded-sampled streams exactly."""
    run_sub("""
        base, _ = run_engine(cache="dense")
        assert any(r.sampling.temperature > 0 for r in requests())
        for shape in ((1, 2), (2, 2)):
            got, eng = run_engine(cache="dense", mesh_shape=shape)
            assert got == base, (shape, got, base)
            assert eng.mesh is not None
        print("dense OK")
        """)


def test_sharded_paged_decode_bitwise_identical():
    run_sub("""
        base, _ = run_engine(cache="paged")
        for shape in ((1, 2), (2, 2)):
            got, _ = run_engine(cache="paged", mesh_shape=shape)
            assert got == base, (shape, got, base)
        print("paged OK")
        """)


def test_sharded_spec_decode_bitwise_identical():
    """Speculative decode (draft -> verify -> accept) over a sharded
    paged engine emits the same streams as the unsharded spec engine."""
    run_sub("""
        base, _ = run_engine(cache="paged", draft_k=3)
        got, _ = run_engine(cache="paged", draft_k=3, mesh_shape=(2, 2))
        assert got == base
        print("spec OK")
        """)


def test_sharded_engine_creates_its_kv_state_split():
    """A mesh engine's KV state (dense stripes, or the paged pool and the
    XLA prefill's slot buffer) spans every mesh device with its KV heads
    split, before and after serving, and is never parked on one device
    (a pool sized for the mesh may not fit one)."""
    run_sub("""
        for cache in ("dense", "paged"):
            m = tiny_model()
            eng = ServeEngine(m, m.init(jax.random.PRNGKey(0)), ServeConfig(
                batch_slots=4, max_len=64, cache=cache, mesh_shape=(1, 2)))
            mesh_devs = set(eng.mesh.devices.flat)
            for when in ("built", "served"):
                state = [eng.caches, eng._pf_buf]
                for leaf in jax.tree.leaves(state):
                    assert leaf.sharding.device_set == mesh_devs, (
                        cache, when, leaf.shape, leaf.sharding)
                    assert not leaf.sharding.is_fully_replicated, (
                        cache, when, leaf.shape, leaf.sharding)
                assert (eng._pf_buf is None) == (cache == "dense")
                for r in requests():
                    eng.submit(r)
                eng.run(max_ticks=500)
        print("layout OK")
        """)


def test_sharded_offer_reports_per_host_pages():
    """Regression: a sharded paged engine's offer() advertises the
    per-host sub-pool split, it sums to the aggregate, and an admitted
    slot's page chain lands entirely on the slot's own host."""
    run_sub("""
        _, eng = run_engine(cache="paged", mesh_shape=(2, 2))
        off = eng.offer()
        assert eng.kv.num_hosts == 2
        by_host = off["free_pages_by_host"]
        assert len(by_host) == 2
        assert sum(by_host) == off["free_pages"], (by_host, off)
        # host-locality of a live chain: admit one request per slot and
        # check every mapped page sits in its slot's sub-pool
        for r in requests(4):
            eng.submit(r)
        eng.step()
        for s in range(eng.slots):
            host = eng.kv.slot_host(s)
            for pg in eng.kv._held[s]:
                assert eng.kv.pool.host_of(pg) == host, (s, pg, host)
        # unsharded engines advertise no per-host split
        _, flat = run_engine(cache="paged")
        assert "free_pages_by_host" not in flat.offer()
        print("offer OK")
        """)


def test_serve_cache_shardings_on_paged_specs():
    """serve_cache_shardings maps paged K/V pools to (page over data,
    KV-head over model) — never the in-page sequence dim — and dense
    stripes to (slot over data, KV-head over model)."""
    run_sub("""
        from jax.sharding import AxisType
        from repro.sharding import (ServeShardFn, serve_cache_shardings,
                                    serve_param_shardings)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        m = tiny_model()
        paged = jax.eval_shape(lambda: m.init_cache_paged(8, 16))
        sh = serve_cache_shardings(mesh, paged, paged=True)
        flat = jax.tree_util.tree_flatten_with_path(sh)[0]
        assert flat, "no cache leaves"
        for path, s in flat:
            spec = tuple(s.spec)
            # trailing dims: (pages, page_size, KV, head) — page dim on
            # "data", KV heads on "model", sequence dim NEVER sharded
            assert spec[-3] is None, (path, spec)
            assert spec[-2] == "model", (path, spec)
            assert spec[-4] == "data", (path, spec)
        dense = jax.eval_shape(lambda: m.init_cache(4, 64))
        dsh = serve_cache_shardings(mesh, dense, paged=False)
        for path, s in jax.tree_util.tree_flatten_with_path(dsh)[0]:
            spec = tuple(s.spec)
            assert spec[-3] is None, (path, spec)  # seq dim replicated
            assert spec[-2] == "model", (path, spec)
        # ServeShardFn is hashable + mesh-keyed: engines over the same
        # mesh share compiled steps through the runtime.steps LRU
        assert ServeShardFn(mesh) == ServeShardFn(mesh)
        assert hash(ServeShardFn(mesh)) == hash(ServeShardFn(mesh))
        # param shardings: ff dim of the MLP up/gate is TP-sharded, the
        # combine (down) projection stays replicated — the gather form
        params = m.param_specs()
        psh = serve_param_shardings(mesh, m.cfg, params)
        blocks = psh["blocks"]
        flat = {jax.tree_util.keystr(p): s for p, s in
                jax.tree_util.tree_flatten_with_path(blocks)[0]}
        for key, s in flat.items():
            spec = tuple(s.spec)
            if "w_gate" in key or "w_up" in key:
                assert spec[-1] == "model", (key, spec)
            if "w_down" in key or "'wo'" in key:
                assert all(a is None for a in spec), (key, spec)
        print("specs OK")
        """)
