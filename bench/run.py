"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload internlm2-chat --seed 7 --seconds 51 \\
        --trace 0

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``,
``bench/configs/<config>.json`` (model, engine shape), ``bench/traffic/
<traffic>.json`` (the mix) and ``bench/traffic/<kind>.py`` (the generator
its ``kind`` names), ``bench/cells/<workload>.json`` (the limits of
the correctness check), ``bench/e2e/<metric>.py`` and ``bench/metrics/
<metric>.py`` (one reader per metric) and ``bench/peaks.json`` (the chip's
peaks by ``device_kind``).

A run: check for a TPU (exit 1 before any work if there is none, or too
few chips); draw the weights on the device from ``--seed``; size the KV
pool with the program's ``fit_device_pool``; build ``ServeEngine``
(continuous, paged, prefix cache on, fcfs, the launcher's TPU knobs);
run every step shape the cell can reach once; then serve the mix's
warm-up and the measured window of ``--seconds``.  ``setup_s`` runs from
process start to the schedule's time 0.  With ``--trace 1`` the window
is recorded by the JAX profiler and the result carries the per-layer
metrics instead of the end-to-end ones.  After the window the generator
waits (a minute at most) for every request it must judge, reads the
device's peak memory, frees the program's state and checks a seeded
sample of finished requests against the plain float32 reference
(``reference.py``): the widest gap by which a served token's logit lies
below the reference's best logit must stay within the cell's limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
DRAIN_S = 60.0  # the longest a request is waited for after the close
sys.path.insert(0, str(BENCH))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_reader(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    mix: dict
    limits: dict
    e2e: list       # BENCHMARK.json end_to_end entries this cell reports
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]


def find_cell(bench: dict, name: str) -> Cell:
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(by_name)}")
    w = by_name[name]

    def applies(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(workload=w,
                config=load_json(BENCH / "configs" / f"{w['config']}.json"),
                mix=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(BENCH / "cells" / f"{name}.json")["limits"],
                e2e=e2e, per_layer=per_layer)


def require_chips(n: int):
    """The devices, or exit 1 when JAX finds no TPU or too few chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform "
                 f"{devices[0].platform!r}")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX found "
                 f"{len(devices)}")
    return devices


def start_jax(chips: int):
    """Point JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache`` (every program, however small), put the program on the
    path, and return the chips (``require_chips``)."""
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return require_chips(chips)


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads as they happen."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.n += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.n += 1


# ---------------------------------------------------------------- program
def build_model(cell: Cell, backend: str):
    """(the program's model, dims) for the cell's configuration, with the
    knobs the launcher serves ``backend`` with; exits where the program's
    architecture departs from what the configuration file states."""
    import jax

    import weights as W
    from repro.configs import get_config
    from repro.launch.serve import serving_knobs
    from repro.models import LM

    prog = cell.config["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    dims = W.Dims.from_config(cell.config["model"])
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.gated_mlp,
           cfg.rope_theta, cfg.tie_embeddings, cfg.qkv_bias, cfg.window)
    want = (dims.layers, dims.d_model, dims.heads, dims.kv_heads,
            dims.head_dim, dims.d_ff, dims.vocab, dims.gated,
            dims.rope_theta, dims.tied, False, 0)
    if got != want:
        raise SystemExit(f"bench: the program's {cfg.name} is {got}, the "
                         f"configuration file states {want}")
    model = LM(cfg, serving_knobs(backend, sharded=False))
    if jax.numpy.dtype(model.knobs.param_dtype) != jax.numpy.dtype(dims.dtype):
        raise SystemExit(f"bench: the program serves "
                         f"{model.knobs.param_dtype.__name__} weights, the "
                         f"configuration file states {dims.dtype}")
    return model, dims


def build_program(cell: Cell, seed: int, devices, *, num_pages=None):
    """(engine, dims, fit note) for the cell's configuration."""
    import jax

    import weights as W
    from repro.launch.pool_fit import fit_device_pool
    from repro.runtime.serve import ServeConfig, ServeEngine

    model, dims = build_model(cell, jax.default_backend())
    params = W.params(dims, seed)
    spec = jax.tree.map(lambda a: (a.shape, a.dtype), model.param_specs())
    mine = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if spec != mine:
        raise SystemExit("bench: the drawn weights do not match the "
                         "program's parameter layout")
    eng = cell.config["engine"]
    shape = dict(slots=eng["slots"], max_len=eng["max_len"],
                 page_size=eng["page_size"], chunk=eng["prefill_chunk"])
    note = ""
    if num_pages is None:
        fit = fit_device_pool(model, devices[0], **shape)
        num_pages = fit.num_pages
        note = (f"pool fit: {num_pages} pages, budget {fit.budget}, step "
                f"peaks {[fp.peak for fp in fit.footprints]}")
    engine = ServeEngine(model, params, ServeConfig(
        batch_slots=eng["slots"], max_len=eng["max_len"],
        page_size=eng["page_size"], prefill_chunk=eng["prefill_chunk"],
        cache="paged", num_pages=num_pages, prefix_cache=True,
        policy="fcfs"))
    return engine, dims, note


def reachable_splits(engine, max_pos: int) -> list:
    """Every split-K fan-out the engine's tick picker can choose for
    positions up to ``max_pos`` and any number of live slots."""
    from repro.runtime.steps import pick_decode_splits

    ps = engine.config.page_size
    return sorted({pick_decode_splits(p, live, max_len=engine.max_len,
                                      page_size=ps)
                   for live in range(1, engine.slots + 1)
                   for p in range(0, max_pos + 1, ps)})


def warm_up(engine, max_pos: int) -> list:
    """Run the prefill-chunk step and every reachable decode variant once
    on the engine's own buffers, with every page-table row unmapped (the
    writes land on the null page).  Returns the split-K variants warmed."""
    import jax
    import jax.numpy as jnp

    table = jnp.zeros_like(jnp.asarray(engine.kv.page_table))
    chunk = jnp.zeros((1, engine.prefill_chunk), jnp.int32)
    buf = () if engine._pf_buf is None else (engine._pf_buf,)  # XLA path
    out = engine._prefill(engine.params, engine.caches, chunk, jnp.int32(0),
                          jnp.int32(0), table, *buf)
    engine.caches = out[1]
    if buf:
        engine._pf_buf = out[2]
    jax.block_until_ready(out[0])
    splits = reachable_splits(engine, max_pos)
    pos = jnp.full((engine.slots,), -1, jnp.int32)
    tokens = jnp.zeros((engine.slots, 1), jnp.int32)
    for s in splits:
        step = engine._step_for_splits(s, False)
        tok, engine.caches = step(engine.params, engine.caches, tokens, pos,
                                  table)
        jax.block_until_ready(tok)
    return splits


# ----------------------------------------------------------------- window
def sample_for_check(recs, seed: int, min_tokens: int, max_requests: int):
    """The finished request with the longest sequence, then others drawn
    from the seed until ``min_tokens`` served tokens are in the sample.
    Where the finished ones hold too few (requests that outlive the
    run), requests still streaming follow, with the tokens served so
    far."""
    import numpy as np

    rng = np.random.default_rng([seed % 2**63, 7])
    done = sorted((r for r in recs if r.done is not None),
                  key=lambda r: r.arrival.index)
    live = sorted((r for r in recs if r.done is None and r.req.output),
                  key=lambda r: r.arrival.index)
    out, n = [], 0
    if done:
        out.append(max(done, key=lambda r: r.prompt_len + len(r.req.output)))
        n = len(out[0].req.output)
    for pool in (done, live):
        rest = [r for r in pool if all(r is not o for o in out)]
        for i in rng.permutation(len(rest)):
            if n >= min_tokens or len(out) >= max_requests:
                return out
            out.append(rest[i])
            n += len(rest[i].req.output)
    return out


def judge(ref, tokens, limits, failed, short):
    """(correct, checks) for ``tokens`` served at the reference's rows
    ``ref``: each number compared beside its limit."""
    import reference as R

    gap = max((float(g.max()) for g in R.gaps(ref, tokens)),
              default=float("inf"))
    checks = {
        "logit_gap": {"value": gap, "limit": limits["logit_gap"]},
        "failed_requests": {"value": failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
        "checked_tokens": {"value": sum(map(len, tokens)),
                           "limit": limits["min_tokens"]},
    }
    correct = (gap <= limits["logit_gap"] and failed == 0 and short == 0
               and checks["checked_tokens"]["value"] >= limits["min_tokens"])
    return correct, checks


def check(dims, seed, sample, limits, failed, short, control=False):
    """Compare the sample with the reference: (correct, checks, control
    verdict).  With ``control`` the control, the reference in fp8 put in
    the program's place, serves its own argmax at each served position
    and is judged by ``judge`` as the program is (for calibrating the
    limit, never in a benchmark run); otherwise the verdict is None."""
    import reference as R

    prompts = [r.arrival.prompt for r in sample]
    outputs = [list(r.req.output) for r in sample]
    seqs, rows = R.served_rows(prompts, outputs)
    t0 = time.perf_counter()
    ref = R.logits(dims, seed, seqs, rows)
    log(f"reference: {len(sample)} requests, "
        f"{sum(map(len, outputs))} served tokens, longest sequence "
        f"{max(map(len, seqs)) if seqs else 0}, "
        f"{time.perf_counter() - t0:.1f}s")
    correct, checks = judge(ref, outputs, limits, failed, short)
    verdict = None
    if control:
        low = R.logits(dims, seed, seqs, rows, lowp=True)
        ok, got = judge(ref, [lg.argmax(axis=1) for lg in low], limits,
                        failed, short)
        verdict = {"correct": bool(ok), "checks": got}
    return correct, checks, verdict


# -------------------------------------------------------------------- run
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             *, num_pages=None, fault=None, control=False) -> dict:
    """One run of ``cell``; the JSON-ready result.  ``num_pages`` skips the
    pool fit and ``fault`` (a callable given the engine) breaks the timed
    path, for tests off the chip; ``control`` reads the check's control
    too (``tune.py``), as ``control``."""
    import jax

    import stats
    from loadgen import LoadGen

    from repro.runtime.serve import Request

    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else {}
    compiles = CompileCounter()
    engine, dims, note = build_program(cell, seed, devices,
                                       num_pages=num_pages)
    log(f"config {cell.config['name']}: {dims}; {note}")
    if fault is not None:
        fault(engine)
    eng = cell.config["engine"]
    mix = cell.mix
    src = load_reader("traffic", mix["kind"]).Source(
        mix, seed, seconds, dims.vocab, eng["slots"])
    admits = []
    kv_admit = engine.kv.admit

    def admit(slot, prompt, max_new):
        res = kv_admit(slot, prompt, max_new)
        if res is not None:
            admits.append((clock(), len(prompt), res.matched, res.start))
        return res

    engine.kv.admit = admit
    splits = warm_up(engine, src.max_position(eng["max_len"]))

    def make_request(a):
        return Request(a.index, a.prompt, max_new_tokens=a.max_new)

    span = jax.profiler.TraceAnnotation if trace else None
    t_zero = time.perf_counter()

    def clock():
        return time.perf_counter() - t_zero

    setup_s = t_zero - T_START
    log(f"setup {setup_s:.3f}s (warmed split-K {splits}, "
        f"{compiles.n} compiles so far)")
    gen = LoadGen(engine, src, make_request, clock=clock, span=span)
    warm = float(mix["warmup_s"])
    gen.run_until(warm)
    queued_at_open = len(engine.queue)
    compiles_before = compiles.n
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
        w0 = clock()
        with jax.profiler.TraceAnnotation("bench.window"):
            gen.run_until(w0 + seconds)
            w1 = clock()
        jax.profiler.stop_trace()
    else:
        w0 = warm
        gen.run_until(w0 + seconds)
        w1 = clock()
    window_compiles = compiles.n - compiles_before
    queued_at_close = len(engine.queue)
    late = [r.submitted - r.due for r in gen.recs if w0 <= r.due < w1]
    measured = src.measured(gen.recs, w0, w1)
    gen.drain(measured, w1, w1 + DRAIN_S)
    # an unfinished request that streamed after the close is late; one
    # with no token since the close, a minute on, never came
    failed = sum(r.done is None and not (r.times and r.times[-1] >= w1)
                 for r in measured)
    short = sum(len(r.req.output) != r.arrival.max_new
                for r in gen.recs if r.done is not None)
    stats_mem = dev.memory_stats() or {}
    peak = int(stats_mem.get("peak_bytes_in_use", 0))
    unfinished = sum(r.done is None for r in measured)
    log(f"window [{w0:.3f}, {w1:.3f}): {len(measured)} requests measured, "
        f"{unfinished} unfinished and {failed} with no token since the "
        f"close, after the drain, "
        f"{window_compiles} compiles in the window, generator late p95 "
        f"{stats.p95_ms(late) or 0:.3f} ms, queued at the close "
        f"{queued_at_close}")

    ctx = types.SimpleNamespace(
        recs=gen.recs, ticks=gen.ticks, admits=admits, w0=w0, w1=w1,
        seconds=w1 - w0, setup_s=setup_s, dims=dims, engine=eng,
        peaks=peaks, trace=None, workload=cell.name,
        load=lambda name: load_reader("metrics", name))
    if trace:
        import devtrace as TR
        from loadgen import SPANS

        ctx.trace = TR.load(TR.find(str(TRACE_DIR)), SPANS)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    metrics = {}
    for m in (cell.per_layer if trace else cell.e2e):
        value = load_reader("metrics" if trace else "e2e", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # free the program's state before the reference runs
    engine.caches = None
    engine.params = None
    del engine
    gen.engine = None
    gc.collect()
    sample = sample_for_check(gen.recs, seed, cell.limits["min_tokens"],
                              cell.limits["max_requests"])
    correct, checks, verdict = check(dims, seed, sample, cell.limits, failed,
                                     short, control)
    result = {
        "correct": bool(correct),
        "attempted": len(measured),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
        "window_compiles": window_compiles,
        "generator_late_p95_ms": stats.p95_ms(late),
        "window": {"start_s": w0, "end_s": w1, "due": len(measured),
                   "unfinished": unfinished, "queued_at_open": queued_at_open,
                   "queued_at_close": queued_at_close, "ticks": sum(
                       w0 <= t.end < w1 for t in gen.ticks)},
    }
    if trace:
        result["device"]["busy_s"] = ctx.trace.busy_s()
        result["device"]["window_s"] = ctx.trace.window_s
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                               "idle_gaps": ctx.trace.top_gaps(10)}
    if verdict is not None:
        result["control"] = verdict
    result["checks"] = checks
    return result


def report_checks(checks: dict) -> None:
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT / "BENCHMARK.json")
    cell = find_cell(bench, args.workload)
    devices = start_jax(cell.workload["chips"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    report_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
