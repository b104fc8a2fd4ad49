"""Step functions: the jit/pjit units the launcher lowers and the scheduler
places.  One train step (grad-accum microbatching + AdamW), one prefill
step, one serve (decode) step — these are the "MPI tasks" of DESIGN.md §2.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp

from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine
from repro.runtime.sampling import (greedy_tokens, sample_tokens,
                                    sample_tokens_multi)


def init_train_state(model, rng, moments_dtype=jnp.float32) -> dict:
    params_f32 = model.init(rng)
    params = jax.tree.map(
        lambda p: p.astype(model.knobs.param_dtype)
        if p.dtype == jnp.float32 else p, params_f32)
    return {"params": params,
            "opt": adamw_init(params_f32, moments_dtype)}


def train_state_specs(model, moments_dtype=jnp.float32) -> dict:
    """Abstract train state (dry-run: no allocation)."""
    return jax.eval_shape(lambda: init_train_state(
        model, jax.random.PRNGKey(0), moments_dtype))


def make_train_step(model, opt_cfg: AdamWConfig, grad_accum: int = 1,
                    accum_dtype=jnp.float32, grad_shardings=None) -> Callable:
    """``accum_dtype=bf16`` halves the gradient-accumulator HBM for 100B+
    models (the AdamW update still runs in fp32).  ``grad_shardings``
    (typically the ZeRO optimizer-state shardings) pins the accumulator to
    a data-sharded layout — ZeRO-2: each microbatch's grads reduce-scatter
    into the shard instead of living replicated across the data axis."""
    schedule = warmup_cosine(opt_cfg)

    def loss_fn(params, mb):
        return model.loss(params, mb)

    def train_step(state, batch):
        params = state["params"]
        if grad_accum == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(accum_dtype), grads)
        else:
            mbs = jax.tree.map(
                lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                    + x.shape[1:]), batch)
            mbs = jax.tree.map(
                lambda x: model.knobs.shard_fn("microbatch", x), mbs)

            def _pin(tree):
                if grad_shardings is None:
                    return tree
                return jax.lax.with_sharding_constraint(tree, grad_shardings)

            def micro(carry, mb):
                gacc, lacc = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params,
                                                                      mb)
                gacc = _pin(jax.tree.map(
                    lambda a, b: a + b.astype(accum_dtype), gacc, g))
                return (gacc, lacc + l), m

            zeros = _pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params))
            (gacc, lsum), ms = jax.lax.scan(micro, (zeros, jnp.float32(0.0)),
                                            mbs)
            grads = jax.tree.map(lambda g: g / grad_accum, gacc)
            metrics = jax.tree.map(lambda m: m.mean(), ms)
            metrics["loss"] = lsum / grad_accum
        new_master, new_opt, om = adamw_update(grads, state["opt"], opt_cfg,
                                               schedule)
        new_params = jax.tree.map(lambda m, p: m.astype(p.dtype), new_master,
                                  params)
        metrics.update(om)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def make_prefill_step(model) -> Callable:
    def prefill_step(params, batch):
        logits, caches = model.prefill(params, batch)
        next_tokens = greedy_tokens(logits)[:, None]
        return next_tokens, caches

    return prefill_step


def make_serve_step(model, sampled: bool = False) -> Callable:
    """Decode step.  ``pos`` is a scalar (lockstep wave batching) or a (B,)
    vector of per-slot positions (ragged continuous batching; free slots
    parked at -1 issue no attention work on the Pallas path).

    ``sampled=True`` grows the signature by the per-slot sampling arrays
    (``temp[B]``, ``top_k[B]``, ``top_p[B]``, ``keys[B, 2]``) and draws
    through ``runtime.sampling.sample_tokens`` — rows with ``temp <= 0``
    still return the bitwise-greedy argmax, so one compiled step serves
    any mix of greedy and sampled requests."""
    def serve_step(params, caches, tokens, pos):
        logits, new_caches = model.decode_step(params, caches, tokens, pos)
        next_tokens = greedy_tokens(logits)[:, None]
        return next_tokens, new_caches

    def sampled_serve_step(params, caches, tokens, pos, temp, top_k, top_p,
                           keys):
        logits, new_caches = model.decode_step(params, caches, tokens, pos)
        next_tokens = sample_tokens(logits, pos, temp, top_k, top_p,
                                    keys)[:, None]
        return next_tokens, new_caches

    return sampled_serve_step if sampled else serve_step


def make_prefill_chunk_step(model, sampled: bool = False) -> Callable:
    """Chunked prefill step: run ONE slot's prompt chunk (1, C) at absolute
    offset through the stack, writing K/V into the batched cache in place.
    Returns (next-token int32 per chunk row (C,), new caches) so the engine
    can read the row of the last real prompt token.

    ``sampled=True`` instead returns a scalar int32: the token drawn from
    logits row ``last_row`` (the last real prompt token on the final
    chunk; pass 0 for don't-care earlier chunks) under the request's
    sampling params — the first generated token.  The fold position is
    the token's absolute position ``offset + last_row``, one below the
    first decode-step fold, so prefill and decode draws never collide."""
    def prefill_chunk_step(params, caches, tokens, slot, offset):
        logits, new_caches = model.prefill_chunk_step(params, caches, tokens,
                                                      slot, offset)
        next_tokens = greedy_tokens(logits)
        return next_tokens, new_caches

    def sampled_chunk_step(params, caches, tokens, slot, offset, last_row,
                           temp, top_k, top_p, key):
        logits, new_caches = model.prefill_chunk_step(params, caches, tokens,
                                                      slot, offset)
        row = jax.lax.dynamic_index_in_dim(logits, last_row, 0,
                                           keepdims=True)
        tok = sample_tokens(row, (offset + last_row)[None], temp[None],
                            top_k[None], top_p[None], key[None])[0]
        return tok, new_caches

    return sampled_chunk_step if sampled else prefill_chunk_step


def make_paged_prefill_chunk_buf_step(model, page_size: int,
                                      sampled: bool = False,
                                      gather: bool = False) -> Callable:
    """Buffered paged chunked prefill (XLA path): threads the per-layer
    dense gather buffer through the step so chunk N reuses chunk N-1's
    slot view instead of re-gathering the full page chain.  Signature
    grows ``buf`` after ``page_idx`` and the step returns
    (tokens, new caches, new buf); ``gather=True`` is the first-chunk
    variant of a prefix-cache hit (rebuilds the view from the table)."""
    def prefill_chunk_step(params, caches, tokens, slot, offset, page_idx,
                           buf):
        logits, new_caches, new_buf = model.prefill_chunk_step_paged_buf(
            params, caches, tokens, slot, offset, page_idx, buf,
            page_size=page_size, gather=gather)
        next_tokens = greedy_tokens(logits)
        return next_tokens, new_caches, new_buf

    def sampled_chunk_step(params, caches, tokens, slot, offset, page_idx,
                           buf, last_row, temp, top_k, top_p, key):
        logits, new_caches, new_buf = model.prefill_chunk_step_paged_buf(
            params, caches, tokens, slot, offset, page_idx, buf,
            page_size=page_size, gather=gather)
        row = jax.lax.dynamic_index_in_dim(logits, last_row, 0,
                                           keepdims=True)
        tok = sample_tokens(row, (offset + last_row)[None], temp[None],
                            top_k[None], top_p[None], key[None])[0]
        return tok, new_caches, new_buf

    return sampled_chunk_step if sampled else prefill_chunk_step


# ------------------------------------------------------------- speculative
def make_spec_serve_step(model, draft_len: int,
                         sampled: bool = False) -> Callable:
    """Speculative verify step: score the feed token plus up to
    ``draft_len`` drafted continuations in ONE forward pass.

    tokens (B, T = draft_len + 1) int32 at absolute positions
    ``pos[b] .. pos[b] + T - 1``; returns (target (B, T) int32, new
    caches) where ``target[b, t]`` is the token the target model emits
    after feed + drafts[:t] — the greedy argmax, or (``sampled=True``)
    the draw of ``sampling.sample_tokens_multi`` with each row's
    absolute position folded into the slot's key.  The engine's host
    side compares drafts against ``target`` (``speculative_accept``) and
    rolls rejected positions back by truncation.
    """
    def spec_step(params, caches, tokens, pos):
        logits, new_caches = model.decode_step_spec(params, caches, tokens,
                                                    pos)
        target = greedy_tokens(logits)
        return target, new_caches

    def sampled_spec_step(params, caches, tokens, pos, temp, top_k, top_p,
                          keys):
        logits, new_caches = model.decode_step_spec(params, caches, tokens,
                                                    pos)
        target = sample_tokens_multi(logits, pos, temp, top_k, top_p, keys)
        return target, new_caches

    return sampled_spec_step if sampled else spec_step


def make_paged_spec_serve_step(model, page_size: int, draft_len: int,
                               sampled: bool = False) -> Callable:
    """Paged mirror of ``make_spec_serve_step`` (adds the page-table
    array; draft K/V land in the slot's mapped pages)."""
    def spec_step(params, caches, tokens, pos, page_idx):
        logits, new_caches = model.decode_step_spec_paged(
            params, caches, tokens, pos, page_idx, page_size=page_size)
        target = greedy_tokens(logits)
        return target, new_caches

    def sampled_spec_step(params, caches, tokens, pos, page_idx, temp,
                          top_k, top_p, keys):
        logits, new_caches = model.decode_step_spec_paged(
            params, caches, tokens, pos, page_idx, page_size=page_size)
        target = sample_tokens_multi(logits, pos, temp, top_k, top_p, keys)
        return target, new_caches

    return sampled_spec_step if sampled else spec_step


# ------------------------------------------------------------------- paged
def make_paged_serve_step(model, page_size: int,
                          sampled: bool = False) -> Callable:
    """Decode step over a paged KV cache: identical to ``make_serve_step``
    plus the scalar-prefetched ``page_idx (B, max_pages)`` page-table
    array (``page_size`` is static); ``sampled=True`` appends the same
    per-slot sampling arrays as the dense variant."""
    def serve_step(params, caches, tokens, pos, page_idx):
        logits, new_caches = model.decode_step_paged(params, caches, tokens,
                                                     pos, page_idx,
                                                     page_size=page_size)
        next_tokens = greedy_tokens(logits)[:, None]
        return next_tokens, new_caches

    def sampled_serve_step(params, caches, tokens, pos, page_idx, temp,
                           top_k, top_p, keys):
        logits, new_caches = model.decode_step_paged(params, caches, tokens,
                                                     pos, page_idx,
                                                     page_size=page_size)
        next_tokens = sample_tokens(logits, pos, temp, top_k, top_p,
                                    keys)[:, None]
        return next_tokens, new_caches

    return sampled_serve_step if sampled else serve_step


def make_paged_prefill_chunk_step(model, page_size: int,
                                  sampled: bool = False) -> Callable:
    """Paged chunked prefill: the (1, C) chunk lands in the physical pages
    the slot's page-table row maps (C a page multiple, offset aligned);
    ``sampled=True`` mirrors ``make_prefill_chunk_step(sampled=True)``."""
    def prefill_chunk_step(params, caches, tokens, slot, offset, page_idx):
        logits, new_caches = model.prefill_chunk_step_paged(
            params, caches, tokens, slot, offset, page_idx,
            page_size=page_size)
        next_tokens = greedy_tokens(logits)
        return next_tokens, new_caches

    def sampled_chunk_step(params, caches, tokens, slot, offset, page_idx,
                           last_row, temp, top_k, top_p, key):
        logits, new_caches = model.prefill_chunk_step_paged(
            params, caches, tokens, slot, offset, page_idx,
            page_size=page_size)
        row = jax.lax.dynamic_index_in_dim(logits, last_row, 0,
                                           keepdims=True)
        tok = sample_tokens(row, (offset + last_row)[None], temp[None],
                            top_k[None], top_p[None], key[None])[0]
        return tok, new_caches

    return sampled_chunk_step if sampled else prefill_chunk_step


# ------------------------------------------------- compiled-step LRU cache
# One module-level cache for every serving step the engines jit.  The
# pre-PR-4 per-engine dict meant each ServeEngine recompiled identical
# steps — every benchmark mode/policy sweep and ci.sh smoke paid XLA
# compilation again for the same (model config, step kind).  Keyed on
# (cfg, knobs, kind, sampled, page_size, draft_len): cfg and RuntimeKnobs
# are frozen dataclasses, so two engines over equal configs share one
# jitted callable (and with it jax's compilation cache).  Bounded LRU;
# falls back to an uncached build if a config is unhashable (custom
# shard_fn closures etc.).
_STEP_KINDS = {
    "serve": lambda m, ps, s, dl: make_serve_step(m, sampled=s),
    "prefill_chunk":
        lambda m, ps, s, dl: make_prefill_chunk_step(m, sampled=s),
    "paged_serve":
        lambda m, ps, s, dl: make_paged_serve_step(m, ps, sampled=s),
    "paged_prefill_chunk":
        lambda m, ps, s, dl: make_paged_prefill_chunk_step(m, ps, sampled=s),
    "paged_prefill_chunk_buf":
        lambda m, ps, s, dl: make_paged_prefill_chunk_buf_step(
            m, ps, sampled=s, gather=False),
    "paged_prefill_chunk_buf_gather":
        lambda m, ps, s, dl: make_paged_prefill_chunk_buf_step(
            m, ps, sampled=s, gather=True),
    "spec_serve": lambda m, ps, s, dl: make_spec_serve_step(m, dl, sampled=s),
    "paged_spec_serve":
        lambda m, ps, s, dl: make_paged_spec_serve_step(m, ps, dl, sampled=s),
    "decode_one": lambda m, ps, s, dl: m.decode_step,
}
# Steps that thread extra donatable state beyond the caches (argnum 1).
# The buffered prefill steps also consume/return the dense gather buffer
# at argnum 6, so donate it too and XLA reuses the allocation per chunk.
_STEP_DONATE = {
    "paged_prefill_chunk_buf": (1, 6),
    "paged_prefill_chunk_buf_gather": (1, 6),
}
_STEP_CACHE: OrderedDict = OrderedDict()
_STEP_CACHE_MAX = 64
_step_cache_hits = 0
_step_cache_misses = 0
_step_build_s = 0.0  # wall seconds spent building/jit-wrapping on misses


def step_cache_stats() -> dict:
    return {"hits": _step_cache_hits, "misses": _step_cache_misses,
            "size": len(_STEP_CACHE), "build_s": _step_build_s}


def compiled_fn(key, build: Callable, donate=()) -> Callable:
    """``jax.jit(build(), donate_argnums=donate)``, memoized in the
    shared bounded LRU.  ``build`` runs only on a miss.  Unhashable keys
    (custom shard_fn closures etc.) fall back to an uncached build.
    The serving engine routes every compiled callable — decode/prefill
    steps and the checkpoint copy_out/copy_in pair — through here, so
    there is exactly one cache to size and instrument."""
    global _step_cache_hits, _step_cache_misses, _step_build_s
    try:
        fn = _STEP_CACHE.get(key)
    except TypeError:
        key = None  # unhashable: build uncached
        fn = None
    if fn is not None:
        _step_cache_hits += 1
        _STEP_CACHE.move_to_end(key)
        return fn
    _step_cache_misses += 1
    t0 = time.perf_counter()
    fn = jax.jit(build(), donate_argnums=donate)
    _step_build_s += time.perf_counter() - t0
    if key is not None:
        _STEP_CACHE[key] = fn
        while len(_STEP_CACHE) > _STEP_CACHE_MAX:
            _STEP_CACHE.popitem(last=False)
    return fn


def compiled_step(model, kind: str, *, sampled: bool = False,
                  page_size: int = 0, decode_splits=None,
                  draft_len: int = 0) -> Callable:
    """Jitted serving step for ``model`` (donating the caches), memoized
    module-wide.  ``decode_splits`` overrides the knob for the split-K
    variants (the autotuner's per-fanout steps share the cache too);
    ``draft_len`` sizes the speculative verify block (spec kinds only —
    each draft depth is its own compiled step)."""
    knobs = (model.knobs if decode_splits is None
             else model.knobs.with_(decode_splits=decode_splits))

    def build():
        mdl = (model if knobs is model.knobs
               else type(model)(model.cfg, knobs))
        return _STEP_KINDS[kind](mdl, page_size, sampled, draft_len)

    return compiled_fn((model.cfg, knobs, kind, sampled, page_size,
                        draft_len), build,
                       donate=_STEP_DONATE.get(kind, (1,)))


# -------------------------------------------------------- split-K autotune
def pick_decode_splits(max_pos: int, batch: int, *, max_len: int,
                       page_size: int = 0, override: int = 0) -> int:
    """Choose the split-K fan-out for this decode tick.

    Split-K buys concurrency on the KV HBM stream: with few live slots
    and a long prefix, one sequential stream under-subscribes the memory
    system, so we split it.  With many live slots the batch axis already
    provides the parallelism and extra splits only pay combine overhead.

    Heuristic: double the splits while (a) each split still covers >= 2k
    tokens of live prefix, (b) total concurrent streams (batch * splits)
    stay <= 32, and (c) the split count divides the kernel's partition
    axis.  The dense kernel partitions the padded cache axis
    (``max_len``); the paged kernel tiles by whole pages, so with
    ``page_size > 0`` the splits must divide ``max_len // page_size``
    (the per-slot page count) — dividing ``max_len`` alone is not
    enough (e.g. max_len=96, page_size=16: 4 divides 96 but not the
    6 pages).  ``override >= 1`` (the ``RuntimeKnobs.decode_splits``
    static knob) bypasses the heuristic but is still clamped down to a
    divisor of the partition axis so a misconfigured knob cannot hand
    the kernel a ragged tiling.
    """
    units = max_len // page_size if page_size > 0 else max_len
    if override >= 1:
        splits = override
        while splits > 1 and units % splits:
            splits -= 1
        return splits
    if max_pos < 2048:
        return 1
    splits = 1
    while (splits < 8
           and max_pos // (2 * splits) >= 2048
           and 2 * splits * max(batch, 1) <= 32
           and units % (2 * splits) == 0):
        splits *= 2
    return splits
