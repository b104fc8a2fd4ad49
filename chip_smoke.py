"""Chip smoke run: serve internlm2-1.8b at its published widths on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # only: (1, 4) mesh engine vs one chip

One chip: the model is built by the launcher's own ``build_serving_model``
(bf16 params, compute and KV cache, Pallas kernels), the paged KV pool is
sized from the compiled steps' memory analysis, and ``ServeEngine`` serves
8 greedy requests with prompts of a few hundred tokens in continuous mode.
The served logits are then checked against an uncached float32 XLA forward
of the same weights: a teacher-forced replay of every request through the
engine's paged Pallas model path (prefill chunks, then decode steps in one
pass and in 8-way split-K), at the engine's slot count and pool size with
the requests on the pool's highest pages in shuffled order, must stay
within ``LOGIT_BOUND`` (largest error) and ``LOGIT_RMS_BOUND`` (rms error)
of the reference over the served positions, and every served token must
be within ``LOGIT_BOUND`` of the reference's best logit.  The engine's own
compiled greedy steps, replayed the same way, must give back every served
token bitwise.  Every request must finish and the pool drain.

``--chips 4`` runs nothing else: the same requests on a (1, 4) mesh engine
(bf16 on XLA attention, the launcher's choice under a mesh) and on one
chip with the same knobs.  The params and pool must be split over all four
devices, with no device holding more than 1.1 times another's bytes; a
teacher-forced replay of both model paths over the one-chip tokens must
agree within the logit bounds; and where the greedy tokens are not bitwise
equal, each engine's tokens must pass the reference check above.

This is a cold smoke run, not a benchmark: its times include compilation.
The last line of stdout is one JSON object, ``{"ok": true, "device": ...}``.
With no TPU it exits nonzero before building anything and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "internlm2-1.8b"
SLOTS, MAX_LEN, PAGE_SIZE, CHUNK = 32, 2048, 16, 32
N_REQUESTS, MAX_NEW = 8, 32
PROMPT_LENS = (40, 480)  # prompt lengths are drawn from this range
# Bounds on |bf16 logit - f32 XLA logit| over the served positions: the
# largest, and the root mean square.  The random-init logits have a standard
# deviation of about 0.9.  At these widths with 2 to 12 of the 24 layers,
# XLA's CPU bf16 path measured max 0.06 to 0.12 and rms 0.011 to 0.023,
# growing about as sqrt(depth); a decode mask off by one position measured
# max 0.86 / rms 0.12 at 4 layers, attention output scaled by 0.9 in prefill
# max 0.41 / rms 0.047.
LOGIT_BOUND, LOGIT_RMS_BOUND = 0.4, 0.06
MESH_SHAPE = (1, 4)
MESH_SLOTS, MESH_MAX_LEN = 8, 1024


def say(msg: str) -> None:
    print(msg, flush=True)


def require(ok, msg) -> None:
    """A check that fails the run; unlike ``assert``, kept under ``-O``."""
    if not ok:
        raise AssertionError(msg)


def require_tpu():
    """The devices, or exit 1 before any work when JAX finds no TPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found platform "
                 f"{devices[0].platform!r}")
    return devices


class CacheEvents:
    """Counts compiles that consulted JAX's persistent compilation cache,
    and how many of them it answered."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits

    def since(self, snap) -> str:
        req, hits = self.requests - snap[0], self.hits - snap[1]
        return f"{hits} of {req} compiles answered"


def make_requests(vocab: int, n: int = N_REQUESTS, max_new: int = MAX_NEW,
                  lens=PROMPT_LENS):
    from repro.runtime.serve import Request

    rng = np.random.default_rng(0)
    return [Request(i, rng.integers(0, vocab, size=int(rng.integers(*lens)))
                    .astype(np.int32), max_new_tokens=max_new)
            for i in range(n)]


def serve(engine, requests):
    """Submit fresh copies of ``requests``, run to drain; outputs by id."""
    for r in requests:
        engine.submit(dataclasses.replace(r, output=[]))
    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0
    by_id = {r.req_id: r for r in done}
    require(sorted(by_id) == [r.req_id for r in requests], sorted(by_id))
    bad = {i: r.finish_reason for i, r in by_id.items()
           if r.finish_reason not in ("length", "eos")}
    require(not bad, f"requests that did not finish: {bad}")
    return [list(by_id[r.req_id].output) for r in requests], wall


def check_drained(engine) -> str:
    """Every slot free, every page table row unmapped, and the only pages
    still in use are prefix-cache entries nobody else holds."""
    kv = engine.kv
    require(all(r is None for r in engine.active), "a slot is still live")
    require(not (kv.page_table != 0).any(), "a page table row is mapped")
    cached = kv.prefix.evictable() if kv.prefix is not None else 0
    require(kv.pool.in_use == cached, f"{kv.pool.in_use} pages in use, "
            f"{cached} of them prefix-cache only")
    return (f"pool drained: {kv.pool.available}/{kv.pool.capacity} pages "
            f"free, {cached} held only by the prefix cache")


def reference_logits(cfg, params, prompts, outputs):
    """Uncached float32 XLA forward of each ``prompt + output[:-1]``:
    (R, N, V) logits at the positions that produced the served tokens
    (rows past a request's output length are don't-care)."""
    import jax
    import jax.numpy as jnp

    from repro.models import LM, RuntimeKnobs
    from repro.models.layers import unembed

    ref = LM(cfg, RuntimeKnobs(compute_dtype=jnp.float32, remat=False,
                               q_chunk=128))
    n = max(len(o) for o in outputs)
    seqs = [np.concatenate([p, np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outputs)]
    length = -(-max(len(s) for s in seqs) // 128) * 128
    tokens = np.zeros((len(seqs), length), np.int32)
    idx = np.zeros((len(seqs), n), np.int32)
    for i, (s, p, o) in enumerate(zip(seqs, prompts, outputs)):
        tokens[i, :len(s)] = s
        idx[i] = len(p) - 1 + np.minimum(np.arange(n), len(o) - 1)

    @jax.jit
    def forward(params, tokens, idx):
        x, _, _ = ref.hidden(params, {"tokens": tokens}, mode="train")
        h = jnp.take_along_axis(x, idx[..., None], axis=1)
        return unembed(params["embed"], h).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        return np.asarray(forward(params, jnp.asarray(tokens),
                                  jnp.asarray(idx)))


def replay_table(r, *, slots, num_pages, max_len, page_size):
    """Page table for a replay of ``r`` requests in slots ``0..r-1``:
    they map the pool's highest ``r * max_len / page_size`` page ids in
    shuffled order; the other slots map nothing."""
    max_pages = max_len // page_size
    need = r * max_pages
    require(need < num_pages, f"{r} page chains of {max_pages} need more "
            f"than the {num_pages - 1} pages of the pool")
    pages = np.random.default_rng(0).permutation(
        np.arange(num_pages - need, num_pages, dtype=np.int32))
    table = np.zeros((slots, max_pages), np.int32)
    table[:r] = pages.reshape(r, max_pages)
    return table


def replay(prefill, decode, params, caches, table, prompts, outputs, *,
           chunk):
    """Teacher-forced replay of the served requests, request ``i`` in slot
    ``i``: each prompt in ``chunk``-token prefill chunks, then one batched
    decode step per served token.  ``prefill(params, caches, tokens (1,
    C), slot, offset, table)`` and ``decode(params, caches, tokens (slots,
    1), pos, table)`` each return ``(rows, caches)`` with one row per
    chunk token or slot (logits, or a greedy step's tokens).  The rows at
    the positions that produced the served tokens, (R, N, ...); rows past
    an output's length stay zero."""
    import jax.numpy as jnp

    slots = table.shape[0]
    table = jnp.asarray(table)
    n = max(len(o) for o in outputs)
    out = None
    for i, p in enumerate(prompts):
        n_chunks = -(-len(p) // chunk)
        padded = np.zeros(n_chunks * chunk, np.int32)
        padded[:len(p)] = p
        for c in range(n_chunks):
            rows, caches = prefill(
                params, caches, jnp.asarray(padded[None, c * chunk:
                                                   (c + 1) * chunk]),
                jnp.int32(i), jnp.int32(c * chunk), table)
        row = np.asarray(rows).reshape(chunk, -1)[
            len(p) - 1 - (n_chunks - 1) * chunk]
        if out is None:
            out = np.zeros((len(prompts), n) + row.shape, row.dtype)
        out[i, 0] = row
    for j in range(1, n):
        live = np.array([j < len(o) for o in outputs]
                        + [False] * (slots - len(outputs)))
        tok = np.zeros((slots, 1), np.int32)
        pos = np.full(slots, -1, np.int32)
        for i, (p, o) in enumerate(zip(prompts, outputs)):
            if live[i]:
                tok[i, 0], pos[i] = o[j - 1], len(p) + j - 1
        rows, caches = decode(params, caches, jnp.asarray(tok),
                              jnp.asarray(pos), table)
        out[live[:len(outputs)], j] = np.asarray(rows).reshape(
            slots, -1)[live]
    return out


def replay_logits(model, params, prompts, outputs, *, slots, num_pages,
                  max_len, page_size, chunk, splits=0, mesh=None):
    """``replay`` through the engine's paged model path (the functions
    ``runtime.steps``' paged steps wrap, same knobs) at the engine's
    ``slots`` and ``num_pages``, on ``replay_table``'s page table.
    ``splits`` sets the decode's split-K fan-out (0: single pass);
    ``mesh`` lays the pool out as a sharded engine does.  (R, N, V)
    logits like ``reference_logits``."""
    import jax

    if splits:
        model = type(model)(model.cfg, model.knobs.with_(decode_splits=splits))
    caches = model.init_cache_paged(num_pages, page_size)
    if mesh is not None:
        from repro.sharding import serve_cache_shardings

        caches = jax.device_put(caches, serve_cache_shardings(mesh, caches,
                                                              paged=True))
    table = replay_table(len(prompts), slots=slots, num_pages=num_pages,
                         max_len=max_len, page_size=page_size)
    prefill = jax.jit(functools.partial(model.prefill_chunk_step_paged,
                                        page_size=page_size),
                      donate_argnums=(1,))
    decode = jax.jit(functools.partial(model.decode_step_paged,
                                       page_size=page_size),
                     donate_argnums=(1,))
    return replay(prefill, decode, params, caches, table, prompts, outputs,
                  chunk=chunk)


def check_served_program(engine, prompts, outputs) -> str:
    """Replay the engine's own compiled greedy steps (its paged prefill
    chunk and single-pass decode, at its slot count and pool size) on
    ``replay_table``'s page table: they must give back every served token
    bitwise."""
    cfg = engine.config
    num_pages = engine.kv.pool.num_pages
    table = replay_table(len(prompts), slots=engine.slots,
                         num_pages=num_pages, max_len=engine.max_len,
                         page_size=cfg.page_size)
    got = replay(engine._prefill, engine._step, engine.params,
                 engine.model.init_cache_paged(num_pages, cfg.page_size),
                 table, prompts, outputs, chunk=cfg.prefill_chunk)
    same = sum(int((got[i, :len(o), 0] == np.asarray(o)).sum())
               for i, o in enumerate(outputs))
    total = sum(map(len, outputs))
    require(same == total, f"the engine's steps replayed give back "
            f"{same}/{total} served tokens")
    return (f"check served program: the engine's compiled steps at "
            f"{engine.slots} slots and {num_pages} pages, replayed on pages "
            f"{int(table[table > 0].min())}..{int(table.max())} in shuffled "
            f"order, give back {same}/{total} served tokens bitwise")


def check_tokens(ref, outputs, bound):
    """Largest gap between the reference's best logit and its logit for
    the served token, over every served position; must be <= bound."""
    gap = 0.0
    for i, o in enumerate(outputs):
        rows = ref[i, :len(o)]
        gap = max(gap, float((rows.max(axis=1)
                              - rows[np.arange(len(o)), o]).max()))
    require(gap <= bound, f"a served token is {gap} below the reference's "
            f"best logit (bound {bound})")
    return gap


def check_logits(name, got, ref, outputs, bound=LOGIT_BOUND,
                 rms_bound=LOGIT_RMS_BOUND):
    """max and rms of |got - ref| over served positions, each within its
    bound."""
    err = max(float(np.abs(got[i, :len(o)] - ref[i, :len(o)]).max())
              for i, o in enumerate(outputs))
    rms = float(np.sqrt(np.mean(np.concatenate(
        [((got[i, :len(o)] - ref[i, :len(o)]) ** 2).ravel()
         for i, o in enumerate(outputs)]))))
    agree = sum(int((got[i, :len(o)].argmax(-1) == np.asarray(o)).sum())
                for i, o in enumerate(outputs))
    total = sum(len(o) for o in outputs)
    say(f"check {name}: |logit difference| max {err:.6f} (bound {bound}), "
        f"rms {rms:.6f} (bound {rms_bound}); argmax is the served token at "
        f"{agree}/{total} positions")
    require(err <= bound and rms <= rms_bound,
            f"{name}: logits off the reference by max {err}, rms {rms}")


def one_chip(devices) -> None:
    import jax

    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.pool_fit import fit_device_pool, step_footprint
    from repro.launch.serve import build_serving_model, enable_compile_cache
    from repro.runtime.serve import ServeConfig, ServeEngine
    from repro.runtime.steps import pick_decode_splits

    say(f"compile cache: {enable_compile_cache()}")
    events = CacheEvents()
    require(ops._on_tpu(), "kernels would run in interpret mode")
    cfg = get_config(ARCH)
    say(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} (random weights, seed 0)")
    model, params = build_serving_model(cfg)
    k = model.knobs
    require(k.use_pallas and k.decode_splits == 0, k)
    say(f"knobs: params={k.param_dtype.__name__} "
        f"compute={k.compute_dtype.__name__} kv={k.cache_dtype.__name__} "
        f"pallas={k.use_pallas} interpret={not ops._on_tpu()}")
    param_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    limit = devices[0].memory_stats()["bytes_limit"]
    say(f"memory: params {param_bytes} bytes, device bytes_limit {limit}")

    shape = dict(slots=SLOTS, max_len=MAX_LEN, page_size=PAGE_SIZE,
                 chunk=CHUNK)
    snap = events.snapshot()
    t0 = time.perf_counter()
    fit = fit_device_pool(model, devices[0], log=say, **shape)
    say(f"pool fit ({time.perf_counter() - t0:.1f}s, persistent cache "
        f"{events.since(snap)}): {fit.num_pages} pages of {PAGE_SIZE} = "
        f"{(fit.num_pages - 1) * PAGE_SIZE} tokens for {SLOTS} slots x "
        f"{MAX_LEN} (dense equivalent {SLOTS * MAX_LEN // PAGE_SIZE + 1}), "
        f"budget {fit.budget} bytes")
    for fp in fit.footprints:
        require(fp.custom_call,
                f"{fp.kind} compiled without a Pallas kernel")
        say(f"  step {fp.kind}: compile {fp.compile_s:.2f}s, argument "
            f"{fp.argument} temp {fp.temp} peak {fp.peak} bytes")

    engine = ServeEngine(model, params, ServeConfig(
        batch_slots=SLOTS, max_len=MAX_LEN, page_size=PAGE_SIZE,
        prefill_chunk=CHUNK, cache="paged", num_pages=fit.num_pages))
    requests = make_requests(cfg.vocab_size)
    prompts = [r.prompt for r in requests]
    say(f"requests: {len(requests)} greedy, prompt lengths "
        f"{[len(p) for p in prompts]}, max_new {MAX_NEW}")
    snap = events.snapshot()
    outputs, wall = serve(engine, requests)
    tokens = sum(map(len, outputs))
    say(f"served {tokens} tokens in {wall:.2f}s wall (cold smoke run "
        f"with first-call set-up, not a benchmark), persistent cache "
        f"{events.since(snap)}")
    longest = max(len(p) for p in prompts) + MAX_NEW
    # one live slot is the count that splits most readily
    pick = pick_decode_splits(longest, 1, max_len=MAX_LEN,
                              page_size=PAGE_SIZE)
    say(f"split-K autotuner pick at max pos {longest}, any live count: "
        f"{pick}")
    require(pick == 1, "the engine decoded with split-K at some tick")
    say(check_drained(engine))
    stats = devices[0].memory_stats()
    say(f"device memory: peak_bytes_in_use {stats['peak_bytes_in_use']} "
        f"(fit predicted step peak {max(fp.peak for fp in fit.footprints)})")
    engine.caches = None  # frees the pool for the checks below
    num_pages = engine.kv.pool.num_pages

    t0 = time.perf_counter()
    ref = reference_logits(cfg, params, prompts, outputs)
    say(f"f32 XLA reference forward: {time.perf_counter() - t0:.1f}s")
    gap = check_tokens(ref, outputs, LOGIT_BOUND)
    say(f"check served tokens: largest gap to the reference's best logit "
        f"{gap:.6f} (bound {LOGIT_BOUND})")
    replays = []
    # 8 splits of 256 tokens: the longer prompts span two, so the
    # combine kernel merges real partial softmaxes
    for splits in (0, 8):
        t0 = time.perf_counter()
        replays.append(replay_logits(model, params, prompts, outputs,
                                     num_pages=num_pages, splits=splits,
                                     **shape))
        say(f"replay with split-K {splits or 1}: "
            f"{time.perf_counter() - t0:.1f}s")
        check_logits(f"paged Pallas split-K {splits or 1} vs f32 XLA",
                     replays[-1], ref, outputs)
    say(f"split-K 8 vs single pass: max |logit difference| "
        f"{float(np.abs(replays[1] - replays[0]).max()):.3g}")
    say(check_served_program(engine, prompts, outputs))
    del engine

    jax.clear_caches()
    snap = events.snapshot()
    fp = step_footprint(model, "paged_serve", num_pages=fit.num_pages,
                        **shape)
    say(f"second compile of paged_serve after clearing in-memory caches: "
        f"{fp.compile_s:.2f}s, persistent cache {events.since(snap)} "
        f"(hit={events.hits > snap[1]})")


def bytes_by_device(arrays, device_ids) -> dict:
    """Bytes each device holds of ``arrays``, from their shardings."""
    out = dict.fromkeys(device_ids, 0)
    for a in arrays:
        shard = a.sharding.shard_shape(a.shape)
        for d in a.sharding.device_set:
            out[d.id] = out.get(d.id, 0) + math.prod(shard) * a.dtype.itemsize
    return out


def four_chips(devices) -> None:
    import jax

    from repro.configs import get_config
    from repro.launch.serve import build_serving_model, enable_compile_cache
    from repro.runtime.serve import ServeConfig, ServeEngine

    require(len(devices) >= 4,
            f"--chips 4 needs 4 devices, got {len(devices)}")
    say(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    model, params = build_serving_model(cfg, mesh_shape=MESH_SHAPE)
    k = model.knobs
    require(not k.use_pallas, k)
    say(f"knobs: params={k.param_dtype.__name__} "
        f"compute={k.compute_dtype.__name__} kv={k.cache_dtype.__name__} "
        f"pallas={k.use_pallas}")
    config = ServeConfig(batch_slots=MESH_SLOTS, max_len=MESH_MAX_LEN,
                         page_size=PAGE_SIZE, prefill_chunk=CHUNK,
                         cache="paged")
    requests = make_requests(cfg.vocab_size)
    prompts = [r.prompt for r in requests]

    sharded = ServeEngine(model, params,
                          dataclasses.replace(config, mesh_shape=MESH_SHAPE))
    mesh_ids = sorted(d.id for d in sharded.mesh.devices.flat)
    leaves = jax.tree.leaves((sharded.params, sharded.caches))
    jax.block_until_ready(leaves)
    per_dev = bytes_by_device(leaves, mesh_ids)
    live = bytes_by_device(jax.live_arrays(), mesh_ids)
    total = sum(leaf.nbytes for leaf in leaves)
    split = {name: sum(not leaf.sharding.is_fully_replicated
                       for leaf in jax.tree.leaves(tree))
             for name, tree in (("params", sharded.params),
                                ("pool", sharded.caches))}
    in_use = {d.id: d.memory_stats()["bytes_in_use"]
              for d in sharded.mesh.devices.flat}
    say(f"mesh {MESH_SHAPE} on devices {mesh_ids}: params+pool {total} "
        f"bytes, held per device {per_dev}; all live arrays per device "
        f"{live}; bytes_in_use {dict(sorted(in_use.items()))}; split "
        f"leaves: params "
        f"{split['params']}/{len(jax.tree.leaves(sharded.params))}, pool "
        f"{split['pool']}/{len(jax.tree.leaves(sharded.caches))}")
    require(len(mesh_ids) == 4, mesh_ids)
    require(all(0 < b < total for b in per_dev.values()), per_dev)
    require(split["pool"] == len(jax.tree.leaves(sharded.caches)), split)
    require(split["params"] > 0, split)
    # no device holds more than its share, e.g. a whole unsharded copy
    require(max(in_use.values()) <= 1.1 * min(in_use.values()), in_use)
    out_mesh, wall = serve(sharded, requests)
    say(f"mesh engine: {sum(map(len, out_mesh))} tokens in {wall:.2f}s "
        f"wall (cold smoke run, compiles included)")
    say(check_drained(sharded))
    mesh, mesh_model, mesh_pages = (sharded.mesh, sharded.model,
                                    sharded.kv.pool.num_pages)
    sharded.caches = None
    del sharded

    one_params = jax.device_put(params, devices[0])
    single = ServeEngine(model, one_params, config)
    out_one, wall = serve(single, requests)
    say(f"one-chip engine: {sum(map(len, out_one))} tokens in {wall:.2f}s "
        f"wall (cold smoke run, compiles included)")
    say(check_drained(single))
    one_pages = single.kv.pool.num_pages
    single.caches = None
    del single
    same = sum(a == b for a, b in zip(out_mesh, out_one))
    say(f"greedy tokens bitwise equal for {same}/{len(requests)} requests")
    shape = dict(slots=MESH_SLOTS, max_len=MESH_MAX_LEN, page_size=PAGE_SIZE,
                 chunk=CHUNK)
    got_mesh = replay_logits(mesh_model, params, prompts, out_one,
                             num_pages=mesh_pages, mesh=mesh, **shape)
    got_one = replay_logits(model, one_params, prompts, out_one,
                            num_pages=one_pages, **shape)
    check_logits(f"mesh {MESH_SHAPE} vs one chip (teacher-forced on the "
                 f"one-chip tokens)", got_mesh, got_one, out_one)
    if same < len(requests):
        for name, out in (("mesh", out_mesh), ("one chip", out_one)):
            ref = reference_logits(cfg, one_params, prompts, out)
            gap = check_tokens(ref, out, LOGIT_BOUND)
            say(f"check {name} tokens: largest gap to the f32 reference's "
                f"best logit {gap:.6f} (bound {LOGIT_BOUND})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the (1, 4) mesh phase")
    args = ap.parse_args(argv)
    devices = require_tpu()
    d = devices[0]
    say(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(devices)
    say(f"smoke phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
