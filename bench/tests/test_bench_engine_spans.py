"""The program's engine tick phases as the trace reduction reads them: on
a hand-made trace, and on one recorded from a tiny engine on the CPU."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace as TR  # noqa: E402


def _spans(trace, name) -> list:
    return [(s, e) for n, s, e in trace.spans if n == name]


def _all_inside(kids, outer) -> bool:
    return bool(kids) and all(any(a <= s and e <= b for a, b in outer)
                              for s, e in kids)


def test_idle_gaps_are_labelled_by_the_engine_phase():
    # the first bench.step holds one engine tick: admission, a prefill
    # with its read-back, and a decode whose read-back covers 40..60
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("copy.2", 20, 40),
                             ("paged_decode_attention.3", 60, 70),
                             ("fusion.4", 95, 120)]}
    spans = [("bench.step", 5, 72), ("bench.observe", 72, 80),
             ("bench.wait", 80, 100), ("bench.step", 90, 130),
             ("engine.step", 6, 71), ("engine.admit", 6, 8),
             ("engine.prefill", 8, 45), ("engine.sync", 30, 44),
             ("engine.decode", 46, 70), ("engine.dispatch", 46, 50),
             ("engine.sync", 50, 69), ("engine.emit", 69, 70)]
    t = TR.Trace(window=(0, 100), ops=ops, modules={}, spans=spans)
    # 0..10 (mid 5) is before the engine's tick began, inside the
    # benchmark's step; 40..60 (mid 50) is the decode's read-back
    assert t.idle_gaps() == [("bench.step", pytest.approx(10e-9)),
                             ("engine.sync", pytest.approx(20e-9)),
                             ("bench.wait", pytest.approx(25e-9))]


@pytest.fixture(scope="module")
def tiny_engine():
    """A tiny paged engine (2 layers, XLA path) on the CPU."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import LM, RuntimeKnobs
    from repro.runtime.serve import ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_config("internlm2-1.8b", smoke=True),
                              num_layers=2, vocab_size=64)
    model = LM(cfg, RuntimeKnobs(cache_dtype=jnp.float32))
    return ServeEngine(model, model.init(jax.random.PRNGKey(0)), ServeConfig(
        batch_slots=2, max_len=64, cache="paged", page_size=8,
        prefill_chunk=16))


def test_a_profiled_engine_leaves_its_phases_nested(tmp_path, tiny_engine):
    """The tiny engine under the JAX profiler: its tick phases land on the
    profiler's host plane, nested as the engine runs them, and
    ``devtrace.load`` keeps them when asked for ``ENGINE_SPANS``."""
    import jax
    import numpy as np

    from repro.runtime.serve import Request
    from repro.runtime.telemetry import ENGINE_SPANS

    eng = tiny_engine
    rng = np.random.default_rng(0)

    def serve(first_id):
        for i in range(2):
            eng.submit(Request(first_id + i, rng.integers(
                1, 60, size=20).astype(np.int32), max_new_tokens=3))
        eng.run()

    serve(0)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(TR.WINDOW):
        serve(10)
    jax.profiler.stop_trace()
    t = TR.load(TR.find(str(tmp_path)), ENGINE_SPANS)

    def inside(child, parent):
        return _all_inside(_spans(t, child), _spans(t, parent))

    assert inside("engine.admit", "engine.step")
    assert inside("engine.prefill", "engine.step")
    assert inside("engine.decode", "engine.step")
    for phase in ("engine.dispatch", "engine.emit"):
        assert inside(phase, "engine.decode")
    syncs = _spans(t, "engine.sync")
    prefills, decodes = _spans(t, "engine.prefill"), _spans(t, "engine.decode")
    assert len(prefills) == 2
    assert len(syncs) == len(prefills) + len(decodes)
    for parent in (prefills, decodes):
        assert all(any(a <= s and e <= b for s, e in syncs)
                   for a, b in parent)
