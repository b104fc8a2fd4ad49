"""Calibration runs on the chip, one process for many runs of one cell.

    python3 bench/tune.py sweep --workload internlm2-chat \\
        --rates 0.6,0.25 --seconds 300 --out out/sweep.jsonl
    python3 bench/tune.py readings --workload internlm2-chat \\
        --seeds 1,2,3 --seconds 51 --out out/readings.jsonl

``sweep`` serves the cell's mix at each offered rate for ``--seconds``
from an empty engine, with no window and no check, and reports the
second half: tokens offered (the outputs of the requests due in it) and
tokens served per second and requests finished; and, over the whole
run, the live slots and the scheduler's queue every 10 s.  Above the knee the
slots stay full, the queue grows and the tokens served per second are the
most the system sustains; the knee in requests per second is that over
the mix's mean output.  At a rate below the knee the queue stays empty
and the tokens served match those offered once the live slots level off.
``readings`` runs the cell as it stands once per seed and reads the
correctness check's number for the program and for its control (the
reference in fp8 put in the program's place, judged by the same check),
from which the cell's limit is set.  Neither is part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

SAMPLE_S = 10.0  # live slots are sampled this often in a sweep


def summary(res: dict) -> dict:
    out = {k: res[k] for k in ("correct", "attempted", "failed",
                               "window_compiles", "window")}
    out["metrics"] = {k: v["value"] for k, v in res["metrics"].items()}
    out["checks"] = {k: v["value"] for k, v in res["checks"].items()}
    out["control"] = {"correct": res["control"]["correct"],
                      **{k: v["value"] for k, v in
                         res["control"]["checks"].items()}}
    out["memory_peak_bytes"] = res["device"]["memory_peak_bytes"]
    return out


def serve_at(cell, rate, seed, seconds, devices, num_pages):
    """Serve ``cell``'s mix at ``rate`` for ``seconds``; the sweep's row
    and the pool size used."""
    import stats
    from loadgen import LoadGen

    from repro.runtime.serve import Request

    engine, dims, note = run.build_program(cell, seed, devices,
                                           num_pages=num_pages)
    num_pages = engine.config.num_pages
    eng = cell.config["engine"]
    mix = dict(cell.mix, rate_per_s=rate, warmup_s=0.0)
    src = run.load_reader("traffic", mix["kind"]).Source(
        mix, seed, seconds, dims.vocab, eng["slots"])
    arrivals = list(src.pending)
    run.warm_up(engine, src.max_position(eng["max_len"]))
    t_zero = time.perf_counter()

    def clock():
        return time.perf_counter() - t_zero

    gen = LoadGen(engine, src, lambda a: Request(
        a.index, a.prompt, max_new_tokens=a.max_new), clock=clock)
    half, live, queue = seconds / 2, [], []
    t = 0.0
    while t < seconds:
        t = min(t + SAMPLE_S, seconds)
        gen.run_until(t)
        live.append(len(gen.live) - gen.queued)
        queue.append(len(engine.queue))
    offered = sum(a.max_new for a in arrivals if half <= a.due < seconds)
    row = dict(
        rate=rate, seconds=seconds, pool_pages=num_pages,
        mean_output=sum(a.max_new for a in arrivals) / len(arrivals),
        offered_tok_s=offered / half,
        served_tok_s=stats.tokens_in(gen.recs, half, seconds) / half,
        finished=sum(1 for r in gen.recs
                     if r.done is not None and half <= r.done < seconds),
        live_every_10s=live, queue_every_10s=queue,
        late_p95_ms=stats.p95_ms([r.submitted - r.due for r in gen.recs]))
    engine.caches = engine.params = None
    del engine, gen
    gc.collect()
    return row, num_pages


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="12345")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = run.find_cell(bench, args.workload)
    devices = run.start_jax(cell.workload["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    with open(args.out, "a") as f:
        def emit(row):
            row = dict(mode=args.mode, workload=cell.name, **row)
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)

        if args.mode == "sweep":
            pages = None
            for rate in [float(r) for r in args.rates.split(",")]:
                row, pages = serve_at(cell, rate, seeds[0], args.seconds,
                                      devices, pages)
                emit(row)
        else:
            for seed in seeds:
                res = run.run_cell(cell, seed, args.seconds, False, devices,
                                   control=True)
                emit(dict(seed=seed, rate=cell.mix["rate_per_s"],
                          **summary(res)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
