"""The open-loop load generator and the end-to-end metrics, on a fake
engine, and the harness's refusals off the chip."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import stats  # noqa: E402
from loadgen import LoadGen  # noqa: E402


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    """Admits up to ``slots`` per tick; each live request gets one token per
    tick (its first in the tick that admits it).  ``tick_s`` gives each
    tick's duration on the clock, by tick number."""

    def __init__(self, clock, tick_s, slots=2):
        self.clock, self.tick_s, self.slots = clock, tick_s, slots
        self.queue, self.live, self.n = [], [], 0

    def submit(self, req):
        self.queue.append(req)

    def step(self):
        while self.queue and len(self.live) < self.slots:
            req = self.queue.pop(0)
            req.state = types.SimpleNamespace(name="PREFILL")
            self.live.append(req)
        self.clock.t += self.tick_s(self.n)
        self.n += 1
        for req in list(self.live):
            req.output.append(7)
            if len(req.output) >= req.max_new:
                req.done = True
                self.live.remove(req)


class Source:
    def __init__(self, arrivals):
        self.pending = sorted(arrivals, key=lambda a: -a.due)

    def poll(self, now, queued):
        out = []
        while self.pending and self.pending[-1].due <= now:
            out.append(self.pending.pop())
        return out

    def next_due(self):
        return self.pending[-1].due if self.pending else None

    def on_done(self, *a):
        pass


def _arrival(i, due, max_new=3, plen=5):
    return types.SimpleNamespace(index=i, due=due, max_new=max_new,
                                 prompt=np.zeros(plen, np.int32))


def _request(a):
    return types.SimpleNamespace(output=[], done=False, max_new=a.max_new,
                                 state=types.SimpleNamespace(name="QUEUED"))


def _drive(arrivals, tick_s, until, slots=2):
    clock = Clock()
    eng = FakeEngine(clock, tick_s, slots)
    drv = LoadGen(eng, Source(arrivals), _request, clock=clock,
                 sleep=clock.sleep)
    drv.run_until(until)
    return drv


def test_requests_are_timed_from_when_they_were_due():
    # the first tick stalls for 5 s; the request due at 1 s waits behind it
    drv = _drive([_arrival(0, 0.0), _arrival(1, 1.0)],
                 lambda n: 5.0 if n == 0 else 0.1, until=10.0)
    late = drv.recs[1]
    assert late.due == 1.0 and late.submitted == pytest.approx(5.0)
    assert late.admitted == pytest.approx(5.0)
    assert late.times[0] == pytest.approx(5.1)


def test_a_request_queued_behind_full_slots_waits_for_a_free_one():
    drv = _drive([_arrival(0, 0.0, max_new=50), _arrival(1, 0.5)],
                 lambda n: 0.4, until=2.0, slots=1)
    assert drv.recs[1].times == [] and drv.recs[1].admitted is None
    assert drv.queued == 1 and len(drv.recs[0].times) == 5


def test_a_gap_open_at_the_close_is_counted():
    # tokens every 0.1 s, then a 10 s tick that the window closes inside
    drv = _drive([_arrival(0, 0.0, max_new=20)],
                 lambda n: 10.0 if n == 4 else 0.1, until=0.45)
    rec = drv.recs[0]
    assert len(rec.times) == 5 and rec.done is None  # 5th token at 10.4
    gaps = stats.itl_s(drv.recs, 0.0, 3.0)
    assert gaps == pytest.approx([0.1, 0.1, 0.1, 3.0 - rec.times[3]])


def test_tokens_per_second_counts_only_the_window():
    drv = _drive([_arrival(i, 0.0, max_new=10) for i in range(2)],
                 lambda n: 0.25, until=3.0)
    # tokens at 0.25, 0.5 and 0.75 fall in [0, 1); the one at 1.0 does not
    assert stats.tokens_in(drv.recs, 0.0, 1.0) == 2 * 3
    assert drv.ticks[1].ctx == [6, 6]  # decode tokens attend p + k


def test_the_generator_sleeps_until_the_next_request_is_due():
    drv = _drive([_arrival(0, 3.0, max_new=1)], lambda n: 0.1, until=5.0)
    assert drv.recs[0].submitted == pytest.approx(3.0)


def test_the_harness_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "internlm2-chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       timeout=300, env=env, cwd=ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "needs a TPU" in p.stderr


def _load_run(bench_dir: Path):
    spec = importlib.util.spec_from_file_location("bench_run_copy",
                                                  bench_dir / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def test_a_new_mix_is_found_from_its_files_alone(tmp_path):
    """A cell added by dropping in a traffic file, a cell file and a
    BENCHMARK.json entry: no file the benchmark already has changes."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = dict(json.loads((BENCH / "traffic" / "chat.json").read_text()),
               rate_per_s=4.0, prompt={"median": 300, "sigma": 0.3,
                                       "min": 100, "max": 600})
    (tmp_path / "bench" / "traffic" / "chat-short.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "cells" / "internlm2-chat-short.json").write_text(
        (BENCH / "cells" / "internlm2-chat.json").read_text())
    bench["workloads"].append({"name": "internlm2-chat-short",
                               "config": "internlm2-1.8b",
                               "traffic": "chat-short", "chips": 1,
                               "why": "short prompts"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "internlm2-chat" in m.get("workloads", []):
            m["workloads"].append("internlm2-chat-short")
    run = _load_run(tmp_path / "bench")
    cell = run.find_cell(bench, "internlm2-chat-short")
    assert cell.mix["rate_per_s"] == 4.0
    assert cell.config["name"] == "internlm2-1.8b"
    assert {m["name"] for m in cell.e2e} == {"out_tok_s", "setup_s"}
    assert len(cell.per_layer) == 7
    src = run.load_reader("traffic", cell.mix["kind"]).Source(
        cell.mix, 5, 51.0, 1000, 32)
    assert all(100 <= len(a.prompt) <= 600 for a in src.pending)
    for m in cell.per_layer + cell.e2e:
        kind = "metrics" if m in cell.per_layer else "e2e"
        assert hasattr(run.load_reader(kind, m["name"]), "read")


def test_every_metric_has_a_reader_and_every_cell_its_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    run = _load_run(BENCH)
    for w in bench["workloads"]:
        cell = run.find_cell(bench, w["name"])
        names = {m["name"] for m in cell.e2e}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
        assert cell.limits["logit_gap"] > 0
    for m in bench["end_to_end"]:
        assert hasattr(run.load_reader("e2e", m["name"]), "read")
    for m in bench["per_layer"]:
        assert hasattr(run.load_reader("metrics", m["name"]), "read")
    assert set(json.loads((BENCH / "peaks.json").read_text())["devices"]) \
        == {"TPU v5 lite"}


def test_the_check_samples_the_longest_finished_then_live_requests():
    run = _load_run(BENCH)

    def rec(i, plen, out, done):
        return types.SimpleNamespace(
            arrival=types.SimpleNamespace(index=i), prompt_len=plen,
            req=types.SimpleNamespace(output=[1] * out),
            done=1.0 if done else None)

    recs = [rec(0, 10, 5, True), rec(1, 500, 50, True),
            rec(2, 20, 5, True), rec(3, 30, 100, False),
            rec(4, 30, 0, False)]
    got = run.sample_for_check(recs, 3, min_tokens=55, max_requests=9)
    assert got[0] is recs[1] and len(got) == 2 and got[1].done
    got = run.sample_for_check(recs, 3, min_tokens=150, max_requests=9)
    assert got[0] is recs[1] and got[-1] is recs[3]
    assert recs[4] not in got
    assert len(run.sample_for_check(recs, 3, 10**6, max_requests=2)) == 2
