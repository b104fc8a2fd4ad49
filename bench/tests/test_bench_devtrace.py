"""The trace reduction: busy union, idle gaps labelled by host span, time
by name, on a hand-made trace and on a short one recorded on the chip."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace as TR  # noqa: E402

MINI = BENCH / "tests" / "data" / "mini.xplane.pb"


def _hand_made():
    # window 0..100 ns; ops overlap at 10..30 and 20..40, one at 60..70,
    # one straddling the close at 95..120
    ops = {"/device:TPU:0": [("fusion.1", 10, 30), ("copy.2", 20, 40),
                             ("paged_decode_attention.3", 60, 70),
                             ("fusion.4", 95, 120)]}
    modules = {"/device:TPU:0": [("jit_serve_step(1)", 10, 70),
                                 ("jit_prefill_chunk_step(2)", 95, 120)]}
    spans = [("bench.step", 5, 72), ("bench.observe", 72, 80),
             ("bench.wait", 80, 100), ("bench.step", 90, 130)]
    return TR.Trace(window=(0, 100), ops=ops, modules=modules, spans=spans)


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = _hand_made()
    # 10..40 (30) + 60..70 (10) + 95..100 (5)
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.window_s == pytest.approx(100e-9)


def test_idle_gaps_are_labelled_by_the_innermost_span():
    t = _hand_made()
    # gaps 0..10 (mid 5: bench.step), 40..60 (mid 50: bench.step),
    # 70..95 (mid 82.5: bench.wait; bench.step 90..130 does not cover it)
    assert t.idle_gaps() == [("bench.step", pytest.approx(10e-9)),
                             ("bench.step", pytest.approx(20e-9)),
                             ("bench.wait", pytest.approx(25e-9))]
    assert t.top_gaps(2) == [["bench.wait", pytest.approx(25e-9)],
                             ["bench.step", pytest.approx(20e-9)]]


def test_time_by_name_is_clipped_to_the_window():
    t = _hand_made()
    assert t.op_time_s(r"^paged_decode_attention") == pytest.approx(10e-9)
    assert t.op_time_s(r"^jit_serve_step", modules=True) == \
        pytest.approx(60e-9)
    assert t.op_time_s(r"^jit_prefill", modules=True) == pytest.approx(5e-9)
    assert t.top_ops(2) == [["fusion", pytest.approx(25e-9)],
                            ["copy", pytest.approx(20e-9)]]


def test_op_names_are_the_hlo_instruction_names():
    assert TR.op_name("%copy.65 = bf16[24,3535]{1,0} copy(bf16[24] %x)") \
        == "copy.65"
    assert TR.op_name("%paged_decode_attention.6 = bf16[32] custom-call("
                      "s32[32] %a)") == "paged_decode_attention.6"
    assert TR.CONTAINER.match("while.4") and not TR.CONTAINER.match(
        "while_fusion.2")
