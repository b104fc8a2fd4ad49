"""The per-layer readers on hand-made records and a hand-made trace, with
the expected numbers worked out here from the definitions."""
import dataclasses
import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace as TR  # noqa: E402
from loadgen import Tick  # noqa: E402

RUN = None


def _run():
    global RUN
    if RUN is None:
        import importlib.util
        spec = importlib.util.spec_from_file_location("bench_run_m",
                                                      BENCH / "run.py")
        RUN = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = RUN
        spec.loader.exec_module(RUN)
    return RUN


def _load(name):
    return _run().load_reader("metrics", name)


# 2 layers, 4 query heads over 2 KV heads of 16, d_model 64, d_ff 128
DIMS = _run().block_dims(json.loads(
    (BENCH / "tests" / "data" / "tiny.json").read_text()))
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def _ctx(**kw):
    # window 0..1 s (generator clock) and 0..1e9 ns (trace clock)
    trace = TR.Trace(
        window=(0, 10**9),
        ops={"/device:TPU:0": [
            ("paged_decode_attention.1", 0, 1000),
            ("paged_prefill_attention.2", 2000, 6000),
            ("fusion.3", 10**8, 3 * 10**8)]},
        modules={"/device:TPU:0": [
            ("jit_serve_step(7)", 0, 2 * 10**6),
            ("jit_prefill_chunk_step(8)", 2 * 10**6, 5 * 10**6)]},
        spans=[])
    base = dict(
        recs=[], w0=0.0, w1=1.0, seconds=1.0, dims=DIMS,
        engine={"prefill_chunk": 32}, peaks=PEAKS, trace=trace,
        ticks=[Tick(0.1, 0.2, [10, 20]), Tick(0.3, 0.4, []),
               Tick(0.5, 0.6, [30]), Tick(1.1, 1.2, [99])],
        admits=[(0.05, 40, 16, 0), (0.5, 100, 64, 64), (2.0, 50, 0, 0)],
        load=_load)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_decode_roofline_is_required_bytes_over_kernel_time():
    # per token attending c positions, per layer: K and V of c positions
    # x 2 KV heads x 16 dims x 2 bytes, plus q and out: 2 x 4 heads x 16 x 2
    ctx_bytes = sum(2 * c * 2 * 16 * 2 + 2 * 4 * 16 * 2 for c in (10, 20, 30))
    need = 2 * ctx_bytes / PEAKS["hbm_bytes_per_s"]  # 2 layers
    flops = 2 * sum(4 * c * 16 * 4 for c in (10, 20, 30))
    assert need > flops / PEAKS["bf16_flops_per_s"]
    got = _load("paged_decode_roofline").read(_ctx())
    assert got == pytest.approx(100 * need / 1000e-9)


def test_step_times_per_tick_and_per_prefilled_token():
    # two decode ticks end in the window; 40 + 36 prompt tokens prefilled
    assert _load("decode_ms_per_tick").read(_ctx()) == pytest.approx(1.0)
    assert _load("prefill_us_per_tok").read(_ctx()) == \
        pytest.approx(3e-3 / 76 * 1e6)


def test_mfu_counts_model_flops_of_the_tokens_served():
    per_tok = 2 * (2 * (64 * 16 * (2 * 4 + 2 * 2) + 3 * 64 * 128)
                   + 256 * 64)
    dec = sum(per_tok + 4 * c * 16 * 4 * 2 for c in (10, 20, 30))
    assert _load("decode_mfu").read(_ctx()) == \
        pytest.approx(100 * dec / (2e-3 * 1e12))


def test_itl_tail_counts_every_gap_and_the_one_open_at_the_close():
    rec = types.SimpleNamespace(times=[0.1, 0.2, 0.4, 0.9], done=None)
    gaps = [0.1, 0.2, 0.5, 0.1]  # the last open from 0.9 to the close
    want = 1e3 * (sorted(gaps)[2] + 0.85 * (sorted(gaps)[3]
                                            - sorted(gaps)[2]))
    got = _load("itl_p95_ms").read(_ctx(recs=[rec]))
    assert got == pytest.approx(want)


def test_hit_share_idle_share_and_silence():
    assert _load("prefix_hit_frac").read(_ctx()) == \
        pytest.approx(100 * 80 / 140)
    idle = 100 * (1 - (1000 + 4000 + 2 * 10**8) / 1e9)
    assert _load("device_idle_frac").read(_ctx()) == pytest.approx(idle)
    quiet = _ctx(ticks=[], admits=[], trace=dataclasses.replace(
        _ctx().trace, ops={}, modules={}))
    for name in ("paged_decode_roofline", "decode_mfu", "decode_ms_per_tick", "prefill_us_per_tok",
                 "prefix_hit_frac", "itl_p95_ms"):
        assert _load(name).read(quiet) is None, name
