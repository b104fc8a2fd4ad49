"""granite-20b in the benchmark: the GPTBigCode block against the program
(its check, its weights, its reference against the engine's paged
prefill-then-decode logits), the closed backlog traffic kind, and the two
readers of the backlog cell on a trace made by hand."""
import dataclasses
import importlib.util
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import devtrace as TR  # noqa: E402
import reference as R  # noqa: E402
import run  # noqa: E402
import weights as W  # noqa: E402
from loadgen import LoadGen, Tick  # noqa: E402

GPT = run.load_block("gpt_bigcode")
BACKLOG = run.load_reader("traffic", "backlog")
SEED = 2**35 + 17
TINY = {"num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 8, "num_key_value_heads": 1,
        "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
        "max_position_embeddings": 128, "layer_norm_epsilon": 1e-5,
        "dtype": "float32", "init_std": 0.2}
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "granite20b-code-backlog"


def _dims():
    return GPT.Dims.from_config(TINY)


def _program_cfg(dims):
    """The program's granite-20b at the tiny block's sizes."""
    from repro.configs import get_config

    return dataclasses.replace(
        get_config("granite-20b"), num_layers=dims.layers,
        d_model=dims.d_model, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab, max_positions=dims.positions)


# ------------------------------------------------------- program check
def test_the_block_passes_granite_and_refuses_its_llama_form():
    dims = _dims()
    cfg = _program_cfg(dims)
    run.check_program(dims, cfg)
    assert dims.arch()["norm_eps"] == 1e-5
    llama = dataclasses.replace(cfg, norm="rmsnorm", norm_eps=1e-6,
                                position_embeddings="rope", max_positions=0,
                                qkv_bias=False, proj_bias=False)
    with pytest.raises(SystemExit) as e:
        run.check_program(dims, llama)
    msg = str(e.value)
    for field in ("norm", "norm_eps", "position_embeddings", "max_positions",
                  "qkv_bias", "proj_bias"):
        assert f"{field}: program " in msg, field
    assert "norm: program 'rmsnorm', file 'layernorm'" in msg
    assert "position_embeddings: program 'rope', file 'learned'" in msg


def test_a_program_without_the_fields_is_refused_naming_them():
    """A program whose ``ArchConfig`` has no norm or position fields (the
    repo before this block) departs on each of them."""
    cfg = _program_cfg(_dims())
    new = {"norm", "norm_eps", "position_embeddings", "max_positions",
           "proj_bias"}
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
              if f.name not in new}
    with pytest.raises(SystemExit) as e:
        run.check_program(_dims(), types.SimpleNamespace(**fields))
    msg = str(e.value)
    assert "norm: program '<absent>', file 'layernorm'" in msg
    assert "position_embeddings: program '<absent>', file 'learned'" in msg


def test_the_cells_program_passes_and_takes_the_drawn_layout():
    cell = run.find_cell(SPEC, CELL)
    model, dims = run.build_model(cell, "tpu")
    assert (dims.layers, dims.d_model, dims.heads, dims.kv_heads,
            dims.head_dim, dims.d_ff, dims.vocab, dims.positions,
            dims.eps) == (13, 6144, 48, 1, 128, 24576, 49152, 8192, 1e-5)
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert cell.config["published"]["num_hidden_layers"] == 52
    assert jnp.dtype(model.knobs.param_dtype) == jnp.bfloat16
    spec = jax.tree.map(lambda a: (a.shape, a.dtype), model.param_specs())
    drawn = jax.tree.map(lambda a: (a.shape, a.dtype),
                         jax.eval_shape(lambda: W.params(dims, SEED)))
    assert spec == drawn
    assert {m["name"] for m in cell.per_layer} == {"step_mfu",
                                                   "paged_prefill_roofline"}
    assert {m["name"] for m in cell.e2e} == {"out_tok_s", "setup_s"}


# ------------------------------------------------------------- weights
def test_the_weights_regenerate_bit_for_bit_and_move_no_draw():
    dims = _dims()
    stacked = W.params(dims, SEED)
    for i in range(dims.layers):
        one = W.layer(dims, SEED, i)
        for a, b in zip(jax.tree.leaves(stacked["blocks"]["stack"]),
                        jax.tree.leaves(one)):
            assert np.array_equal(np.asarray(a[i]), np.asarray(b))
    outer = W.head(dims, SEED)
    assert set(outer) == {"embed", "pos_embed", "final_norm"}
    # the table, the positions and the final norm are drawn where the
    # dense block draws its table and norm and the test block
    # bias_ln_pos its positions and norm bias
    dense = run.load_block("dense").Dims(
        layers=2, d_model=64, heads=8, kv_heads=1, head_dim=16, d_ff=128,
        vocab=256, gated=False, rope_theta=1e4, eps=1e-6, tied=True,
        dtype="float32", init_std=0.2)
    was = W.head(dense, SEED)
    assert np.array_equal(outer["embed"]["table"], was["embed"]["table"])
    assert np.array_equal(outer["final_norm"]["scale"],
                          was["final_norm"]["scale"])
    spec = importlib.util.spec_from_file_location(
        "bench_test_block_bias_ln_pos_gpt",
        BENCH / "tests" / "data" / "blocks" / "bias_ln_pos.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    test_block = W.head(mod.Dims.from_config(TINY), SEED)
    assert np.array_equal(outer["pos_embed"]["table"],
                          test_block["pos_embed"]["table"])
    assert np.array_equal(outer["final_norm"]["bias"],
                          test_block["final_norm"]["bias"])


# ---------------------------------------- the engine against the block
PROMPTS = [((np.arange(n) * k + 3) % 256).astype(np.int32)
           for n, k in ((37, 7), (70, 13), (20, 5))]
N_NEW = 12
PAGE, CHUNK, MAX_LEN = 16, 32, 128


def _model(use_pallas):
    from repro.models import LM, RuntimeKnobs

    return LM(_program_cfg(_dims()),
              RuntimeKnobs(cache_dtype=jnp.float32, use_pallas=use_pallas))


def _serve(model, params):
    """The prompts through ``ServeEngine`` (continuous, paged, fcfs, two
    slots for three requests): the tokens it served."""
    from repro.runtime.serve import Request, ServeConfig, ServeEngine

    eng = ServeEngine(model, params, ServeConfig(
        batch_slots=2, max_len=MAX_LEN, cache="paged", page_size=PAGE,
        prefill_chunk=CHUNK, num_pages=2 * MAX_LEN // PAGE + 1,
        policy="fcfs"))
    reqs = [Request(i, p, max_new_tokens=N_NEW)
            for i, p in enumerate(PROMPTS)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return [list(r.output) for r in reqs]


def _paged_logits(model, params, prompt, served):
    """The logits the paged prefill chunk and paged decode steps give at
    the rows that chose each served token, fed the served tokens."""
    caches = model.init_cache_paged(MAX_LEN // PAGE + 1, PAGE)
    table = jnp.asarray(np.arange(1, MAX_LEN // PAGE + 1,
                                  dtype=np.int32)[None])
    prefill = jax.jit(model.prefill_chunk_step_paged,
                      static_argnames="page_size")
    decode = jax.jit(model.decode_step_paged, static_argnames="page_size")
    p = len(prompt)
    padded = np.zeros(-(-p // CHUNK) * CHUNK, np.int32)
    padded[:p] = prompt
    for c in range(0, len(padded), CHUNK):
        lg, caches = prefill(params, caches,
                             jnp.asarray(padded[None, c:c + CHUNK]),
                             jnp.int32(0), jnp.int32(c), table,
                             page_size=PAGE)
    rows = [np.asarray(lg)[(p - 1) - (len(padded) - CHUNK)]]
    for k in range(1, len(served)):
        lg, caches = decode(params, caches,
                            jnp.asarray([[served[k - 1]]], jnp.int32),
                            jnp.asarray([p + k - 1], jnp.int32), table,
                            page_size=PAGE)
        rows.append(np.asarray(lg)[0])
    return np.stack(rows)


def _reference(outputs):
    seqs, rows = R.served_rows(PROMPTS, outputs)
    return R.logits(_dims(), SEED, seqs, rows)


# The program runs in float32 here, as the reference does; they differ by
# the order of summation alone (blocked and online softmax, fused bias
# adds), about 1e-6 of logits of size 1-10 at init_std 0.2.  1e-4 leaves
# that a hundred times of room, and a position read one row off moves the
# logits by more than 1e-1.
TOL = 1e-4


@pytest.mark.parametrize("use_pallas", [False, True])
def test_the_engine_serves_the_references_logits(use_pallas):
    model = _model(use_pallas)
    params = W.params(_dims(), SEED)
    outputs = _serve(model, params)
    assert all(len(o) == N_NEW for o in outputs)
    ref = _reference(outputs)
    # every served token is the reference's best, to within TOL
    assert max(float(g.max()) for g in R.gaps(ref, outputs)) < TOL
    for prompt, out, r in zip(PROMPTS, outputs, ref):
        got = _paged_logits(model, params, prompt, out)
        assert np.abs(got - r).max() < TOL


def test_a_table_read_one_row_off_fails_the_comparison():
    model = _model(False)
    params = W.params(_dims(), SEED)
    table = params["pos_embed"]["table"]
    shifted = dict(params, pos_embed={"table": jnp.roll(table, -1, axis=0)})
    outputs = _serve(model, shifted)
    ref = _reference(outputs)
    worst = max(np.abs(_paged_logits(model, shifted, p, o) - r).max()
                for p, o, r in zip(PROMPTS, outputs, ref))
    assert worst > 1000 * TOL


# ------------------------------------------------------ backlog traffic
MIX = json.loads((BENCH / "traffic" / "code-backlog.json").read_text())


def test_the_backlog_starts_full_and_replaces_each_completion():
    src = BACKLOG.Source(MIX, 5, 51.0, 1000, 32)
    first = src.poll(0.0, 0)
    assert [a.index for a in first] == list(range(64))
    assert all(a.due == 0.0 for a in first) and src.next_due() is None
    for a in first[:3]:
        src.on_done(a, [1] * a.max_new, 7.5)
    assert src.next_due() == 7.5 and src.poll(7.0, 61) == []
    more = src.poll(7.5, 61)
    assert [a.index for a in more] == [64, 65, 66]
    assert src.max_position(8192) == 6144 + 512
    plen, olen = zip(*(src.lengths(i) for i in range(2000)))
    assert min(plen) >= 256 and max(plen) <= 6144
    assert min(olen) >= 16 and max(olen) <= 512
    assert 1800 < np.median(plen) < 2300 and 110 < np.median(olen) < 150


def test_the_backlog_schedule_is_the_same_for_two_run_seeds():
    a = BACKLOG.Source(MIX, 5, 51.0, 1000, 32)
    b = BACKLOG.Source(MIX, 2**33 + 9, 51.0, 1000, 32)
    assert [a.lengths(i) for i in range(600)] == [b.lengths(i)
                                                  for i in range(600)]
    pa, pb = a.poll(0.0, 0), b.poll(0.0, 0)
    assert [(len(x.prompt), x.max_new) for x in pa] == [
        (len(x.prompt), x.max_new) for x in pb]
    assert not np.array_equal(pa[0].prompt, pb[0].prompt)  # ids: the seed


class _FakeEngine:
    """Two slots; each live request gets one token a tick."""

    def __init__(self):
        self.queue, self.slots = [], []

    def submit(self, req):
        req.state = types.SimpleNamespace(name="QUEUED")
        self.queue.append(req)

    def step(self):
        while self.queue and len(self.slots) < 2:
            req = self.queue.pop(0)
            req.state = types.SimpleNamespace(name="RUNNING")
            self.slots.append(req)
        for req in self.slots:
            req.output.append(0)
            req.done = len(req.output) >= req.max_new
        self.slots = [r for r in self.slots if not r.done]


def test_the_backlog_keeps_its_outstanding_through_the_load_generator():
    mix = dict(MIX, outstanding=5, output={"median": 3, "sigma": 0.5,
                                           "min": 2, "max": 4})
    src = BACKLOG.Source(mix, 1, 10.0, 50, 2)
    now = [0.0]

    def clock():
        now[0] += 0.01
        return now[0]

    def request(a):
        return types.SimpleNamespace(index=a.index, output=[], done=False,
                                     max_new=a.max_new)

    gen = LoadGen(_FakeEngine(), src, request, clock=clock)
    gen.run_until(3.0)
    finished = [r for r in gen.recs if r.done is not None]
    assert len(finished) > 10
    # outstanding: submitted and unfinished, or due since the last poll
    assert len(gen.recs) - len(finished) + len(src.due) == 5
    assert all(len(r.req.output) == r.arrival.max_new for r in finished)
    w0, w1 = 1.0, 2.0
    measured = src.measured(gen.recs, w0, w1)
    assert measured and all(r.times[0] < w1 for r in measured)
    assert all(r.done is None or r.done >= w0 for r in measured)
    queued = [r for r in gen.recs if not r.times]
    assert queued and not any(r in measured for r in queued)


# ------------------------------------------------------------- readers
DIMS = types.SimpleNamespace(layers=2, d_model=8, heads=4, kv_heads=1,
                             head_dim=2, d_ff=16, vocab=10, gated=False)


def _ctx(trace):
    return types.SimpleNamespace(
        dims=DIMS, w0=0.0, w1=10.0, trace=trace,
        peaks={"bf16_flops_per_s": 1e10, "hbm_bytes_per_s": 1e9},
        engine={"prefill_chunk": 4},
        admits=[(1.0, 6, 0, 0), (5.0, 3, 0, 0), (12.0, 9, 0, 0)],
        ticks=[Tick(2.0, 3.0, [7, 8]), Tick(9.0, 11.0, [5])],
        load=lambda name: run.load_reader("metrics", name))


def _trace():
    ms = 10**6
    dev = "/device:TPU:0"
    return TR.Trace(
        window=(0, 10_000 * ms),
        ops={dev: [("paged_prefill_attention.3", 1_000 * ms,
                    1_000 * ms + 3_920),
                   ("paged_prefill_attention.3", 9_999 * ms + 999_800,
                    10_000 * ms + 200),  # half in the window
                   ("paged_decode_attention.1", 2_000 * ms, 2_001 * ms)]},
        modules={dev: [("jit_prefill_chunk_step(7)", 1_000 * ms,
                        1_000 * ms + 60_000),
                       ("jit_serve_step(9)", 2_000 * ms, 2_000 * ms + 40_000),
                       ("jit__threefry_seed(1)", 3_000 * ms, 3_001 * ms)]},
        spans=[])


def test_step_mfu_on_a_hand_made_trace():
    # token_flops: 2 * (2 * (160 + 256) + 80) + 64 * c = 1824 + 64 c
    prefill = 9 * 1824 + 64 * (21 + 6)   # positions 0-5 and 0-2
    decode = 2 * 1824 + 64 * (7 + 8)     # the tick that ends in the window
    mfu = run.load_reader("metrics", "step_mfu")
    got = mfu.read(_ctx(_trace()))
    assert got == pytest.approx(100.0 * (prefill + decode) / (1e-4 * 1e10))
    assert mfu.read(_ctx(None)) is None


def test_paged_prefill_roofline_on_a_hand_made_trace():
    roof = run.load_reader("metrics", "paged_prefill_roofline")
    # chunks of 4: rows 0-3 and 4-5 of the first prompt, 0-2 of the second
    assert roof.chunk_work(DIMS, 0, 4) == (4.0 * 10 * 2 * 4 * 2,
                                           (2 * 4 * 2 * 2 + 2 * 4 * 4 * 2 * 2)
                                           * 2.0)
    assert roof.chunk_work(DIMS, 4, 6) == (4.0 * 11 * 16, (48 + 64) * 2.0)
    assert roof.prompt_work(DIMS, 0, 6, 4) == (640.0 + 704.0, 320.0 + 224.0)
    assert roof.prompt_work(DIMS, 4, 6, 4) == (704.0, 224.0)  # cached 0-3
    flops, nbytes = 640 + 704 + 384, 320 + 224 + 240
    need = max(flops / 1e10, nbytes / 1e9)  # bytes bind
    busy = (3_920 + 200) * 1e-9
    got = roof.read(_ctx(_trace()))
    assert got == pytest.approx(100.0 * need / busy)
    assert roof.read(_ctx(None)) is None


# ------------------------------------------------ a whole run off the chip
DATA = BENCH / "tests" / "data"


def _tiny_cell():
    return run.Cell(
        workload={"name": "tiny-gpt-bigcode-backlog", "chips": 1},
        config=json.loads((DATA / "tiny-gpt-bigcode.json").read_text()),
        mix=json.loads((DATA / "tiny-backlog.json").read_text()),
        # the float32 program against the float32 reference: equal to
        # rounding
        limits={"logit_gap": 1e-3, "min_tokens": 100, "max_requests": 12},
        e2e=SPEC["end_to_end"], per_layer=[])


def _shift_positions(engine):
    table = engine.params["pos_embed"]["table"]
    engine.params = dict(engine.params, pos_embed={
        "table": jnp.roll(table, -1, axis=0)})


@pytest.mark.parametrize("fault", [None, _shift_positions])
def test_a_whole_backlog_run_is_judged_on_the_block(fault):
    res = run.run_cell(_tiny_cell(), 2**34 + 99, 3.0, False, jax.devices(),
                       num_pages=65, fault=fault)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["window"]["queued_at_close"] > 0  # the backlog never empties
    assert res["checks"]["checked_tokens"]["value"] >= 100
    assert res["correct"] is (fault is None), res["checks"]
