"""The traffic generator: seeded, clipped, Poisson at the mix's rate, and
found by the kind its mix names."""
import importlib.util
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import traffic as T  # noqa: E402

MIXES = ["chat"]


def _load_run(bench_dir: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(bench_dir)))}", bench_dir / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


RUN = _load_run(BENCH)


def _source(m, seed, seconds, vocab=50_000, slots=32):
    return RUN.load_reader("traffic", m["kind"]).Source(m, seed, seconds,
                                                        vocab, slots)


def _schedule(mix, seed, seconds=20.0):
    m = T.load(mix)
    src = _source(m, seed, seconds)
    return src.poll(m["warmup_s"] + seconds, 0)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_gives_the_same_schedule(mix):
    a, b = _schedule(mix, 2**33 + 1), _schedule(mix, 2**33 + 1)
    assert len(a) == len(b) > 10
    for x, y in zip(a, b):
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_a_new_seed_serves_the_same_work_with_new_tokens(mix):
    a, b = _schedule(mix, 11), _schedule(mix, 2**40 + 3)
    assert [(x.due, len(x.prompt), x.max_new) for x in a] == \
        [(x.due, len(x.prompt), x.max_new) for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_fall_within_their_clips(mix):
    m = T.load(mix)
    for a in _schedule(mix, 3):
        assert m["output"]["min"] <= a.max_new <= m["output"]["max"]
        assert m["prompt"]["min"] <= len(a.prompt) <= m["prompt"]["max"]


def test_open_arrivals_fill_the_span_at_the_rate():
    m = T.load("chat")
    src = _source(m, 5, 51.0, 1000)
    dues = [a.due for a in src.pending]
    span = m["warmup_s"] + 51.0
    assert len(dues) == round(m["rate_per_s"] * span)
    assert 0 < min(dues) and max(dues) < span
    assert src.max_position(2048) == min(
        m["prompt"]["max"] + m["output"]["max"], 2047)


def test_a_run_answers_for_the_requests_due_in_its_window():
    src = _source(T.load("chat"), 5, 51.0, 1000)
    recs = [type("R", (), {"due": d})() for d in (0.5, 1.0, 1.9, 2.0)]
    assert [r.due for r in src.measured(recs, 1.0, 2.0)] == [1.0, 1.9]


def test_a_new_kind_is_found_from_its_file_alone(tmp_path):
    """A mix whose ``kind`` names a generator file dropped into
    ``traffic/``: no file the benchmark already has changes."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "bench" / "traffic" / "every-second.py").write_text(
        "import traffic as T\n"
        "class Source:\n"
        "    def __init__(self, mix, seed, seconds, vocab, slots):\n"
        "        self.n = int(seconds)\n"
        "    def poll(self, now, queued):\n"
        "        return [T.Arrival(0, now, [1, 2], 3)]\n")
    run = _load_run(tmp_path / "bench")
    src = run.load_reader("traffic", "every-second").Source(
        {"kind": "every-second"}, 1, 7.0, 100, 4)
    assert src.n == 7 and src.poll(2.0, 0)[0].max_new == 3
