"""Transformer assembly: stacked layers consumed by ``jax.lax.scan``.

Layer parameters are stored *stacked* over a leading layer axis so the whole
depth lowers to a single scanned HLO body (compile time and HLO size stay
O(1) in depth — essential for the 94-layer dry-runs).

Three structural plans (see DESIGN.md):

* uniform   — L identical blocks (dense / moe / ssm / swa archs).
* grouped   — repeating groups of (period-1) inner blocks + 1 outer block
              (gemma3: 5 local-window layers + 1 global layer), plus a
              remainder stack.  Window sizes stay *static* per call site so
              the sliding-window KV slicing lowers to static shapes.
* grouped+shared — zamba2: groups of 6 mamba2 blocks followed by ONE shared
              transformer block (weights reused across groups; per-group KV
              caches at decode).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import attention as attn
from .layers import mlp, mlp_init, rmsnorm, rmsnorm_init
from .moe import moe_ffn, moe_init
from .ssm import ssm_decode_step, ssm_forward, ssm_init, ssm_init_cache

MOE_AUX_KEYS = ("moe_lb_loss", "moe_z_loss", "moe_drop_frac")


@dataclasses.dataclass(frozen=True)
class Plan:
    kind: str  # "uniform" | "grouped"
    n_layers: int
    inner_kind: str  # "attn" | "ssm"
    inner_window: int = 0
    # grouped only:
    period: int = 0  # group size incl. outer block (gemma3: 6)
    n_groups: int = 0
    inner_per_group: int = 0
    remainder: int = 0
    outer_kind: Optional[str] = None  # "attn"
    outer_window: int = 0
    outer_shared: bool = False  # zamba2


def build_plan(cfg) -> Plan:
    if cfg.family == "hybrid":
        p = cfg.shared_attn_period
        return Plan(
            kind="grouped", n_layers=cfg.num_layers, inner_kind="ssm",
            period=p, n_groups=cfg.num_layers // p, inner_per_group=p,
            remainder=cfg.num_layers % p, outer_kind="attn", outer_window=0,
            outer_shared=True,
        )
    if cfg.local_global_period:
        p = cfg.local_global_period
        return Plan(
            kind="grouped", n_layers=cfg.num_layers, inner_kind="attn",
            inner_window=cfg.local_window, period=p,
            n_groups=cfg.num_layers // p, inner_per_group=p - 1,
            remainder=cfg.num_layers % p, outer_kind="attn", outer_window=0,
        )
    if cfg.family == "ssm":
        return Plan(kind="uniform", n_layers=cfg.num_layers, inner_kind="ssm")
    return Plan(kind="uniform", n_layers=cfg.num_layers, inner_kind="attn",
                inner_window=cfg.window)


# ===================================================================== init
def _init_attn_block(key, cfg, dtype, ffn: str):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn.attention_init(
            ks[0], d_model=cfg.d_model, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            qkv_bias=cfg.qkv_bias, dtype=dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
    }
    if ffn == "moe":
        p["moe"] = moe_init(ks[1], cfg.d_model, cfg.moe, dtype)
    elif ffn == "mlp":
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
    return p


def _init_ssm_block(key, cfg, dtype):
    return {"ln": rmsnorm_init(cfg.d_model, dtype),
            "ssm": ssm_init(key, cfg.d_model, cfg.ssm, dtype)}


def _ffn_kind(cfg) -> str:
    return "moe" if cfg.moe is not None else ("mlp" if cfg.d_ff else "none")


def _stack(key, n, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_blocks(key, cfg, dtype):
    plan = build_plan(cfg)
    ffn = _ffn_kind(cfg)
    if plan.inner_kind == "attn":
        inner_init = lambda k: _init_attn_block(k, cfg, dtype, ffn)
    else:
        inner_init = lambda k: _init_ssm_block(k, cfg, dtype)
    if plan.kind == "uniform":
        return {"stack": _stack(key, plan.n_layers, inner_init)}
    ks = jax.random.split(key, 3)
    blocks = {
        "inner": jax.vmap(lambda kk: _stack(kk, plan.inner_per_group, inner_init))(
            jax.random.split(ks[0], plan.n_groups)),
    }
    if plan.remainder:
        blocks["rem"] = _stack(ks[1], plan.remainder, inner_init)
    if plan.outer_shared:
        blocks["outer"] = _init_attn_block(ks[2], cfg, dtype, "mlp")
    else:
        blocks["outer"] = _stack(ks[2], plan.n_groups,
                                 lambda k: _init_attn_block(k, cfg, dtype, ffn))
    return blocks


# ============================================================ block bodies
def _zero_aux(cfg):
    if cfg.moe is not None:
        return {k: jnp.float32(0.0) for k in MOE_AUX_KEYS}
    return {}


def _acc_aux(aux, new):
    if not aux:
        return aux
    return {k: aux[k] + new.get(k, 0.0) for k in aux}


def _apply_attn_block(p, x, positions, *, cfg, window, knobs, collect_cache,
                      ffn, shard_fn):
    h = rmsnorm(p["ln1"], x)
    q, k, v = attn.qkv_project(p["attn"], h, positions, cfg.rope_theta)
    q = shard_fn("attn_q", q)
    k = shard_fn("attn_kv", k)
    v = shard_fn("attn_kv", v)
    if knobs.use_pallas:
        from repro.kernels import flash_attention as _pallas_flash

        blk = min(knobs.q_chunk, q.shape[1])
        ctx = _pallas_flash(q, k, v, causal=True, window=window,
                            block_q=blk, block_k=blk)
    else:
        ctx = attn.flash_attention_xla(q, k, v, causal=True, window=window,
                                       q_chunk=knobs.q_chunk,
                                       causal_skip=knobs.causal_skip)
    ctx = shard_fn("attn_out", ctx)
    x = x + attn.attn_output(p["attn"], ctx)
    h2 = rmsnorm(p["ln2"], x)
    aux = {}
    if ffn == "moe":
        out, aux = moe_ffn(p["moe"], h2, cfg.moe, train=not collect_cache,
                           shard_fn=shard_fn)
    elif ffn == "mlp":
        out = mlp(p["mlp"], h2, cfg.gated_mlp, shard_fn=shard_fn)
    else:
        out = jnp.zeros_like(h2)
    x = x + out
    x = shard_fn("hidden", x)
    cache = ({"k": k.astype(knobs.cache_dtype), "v": v.astype(knobs.cache_dtype)}
             if collect_cache else None)
    return x, aux, cache


@jax.named_scope("mlp")
def _ffn_out(p, x, ffn, *, cfg, shard_fn):
    """Inference-time FFN sublayer (norm, FFN, residual) shared by the
    cached block bodies."""
    h2 = rmsnorm(p["ln2"], x)
    if ffn == "moe":
        out, _ = moe_ffn(p["moe"], h2, cfg.moe, train=False, shard_fn=shard_fn)
    elif ffn == "mlp":
        out = mlp(p["mlp"], h2, cfg.gated_mlp, shard_fn=shard_fn)
    else:
        out = jnp.zeros_like(h2)
    return x + out


def _apply_attn_block_decode(p, x, cache, pos, *, cfg, window, knobs, ffn,
                             shard_fn, paged=None):
    x, new_cache = _attn_decode(p, x, cache, pos, cfg=cfg, window=window,
                                knobs=knobs, shard_fn=shard_fn, paged=paged)
    return _ffn_out(p, x, ffn, cfg=cfg, shard_fn=shard_fn), new_cache


@jax.named_scope("attention")
def _attn_decode(p, x, cache, pos, *, cfg, window, knobs, shard_fn,
                 paged=None):
    """``paged = (page_idx, page_size)`` switches the cache from a dense
    per-slot stripe to a shared page pool addressed through the slot's
    page-table row; attention masking is identical either way.

    x may carry T > 1 tokens per slot (the speculative verify block):
    token ``t`` sits at absolute position ``pos[b] + t``, all T K/V pairs
    are written to the cache first, and the attention mask is causal
    within the block as well as against the prefix."""
    b, t = x.shape[0], x.shape[1]
    h = rmsnorm(p["ln1"], x)
    pos = jnp.asarray(pos, jnp.int32)  # scalar (lockstep) or (B,) (ragged)
    positions = jnp.broadcast_to(
        (pos.reshape(-1, 1) if pos.ndim else pos) + jnp.arange(t)[None, :]
        if t > 1 else (pos.reshape(-1, 1) if pos.ndim else pos), (b, t))
    q, k_new, v_new = attn.qkv_project(p["attn"], h, positions, cfg.rope_theta)
    if paged is not None:
        page_idx, page_size = paged
        quant = "k_scale" in cache  # quantized pools carry scale leaves
        if quant:
            upd = attn.paged_cache_update_multi_quant if t > 1 \
                else attn.paged_cache_update_quant
            kc, vc, ksc, vsc = upd(
                cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
                k_new, v_new, pos, page_idx, page_size)
            new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
        else:
            upd = attn.paged_cache_update_multi if t > 1 \
                else attn.paged_cache_update
            kc, vc = upd(cache["k"], cache["v"], k_new, v_new, pos, page_idx,
                         page_size)
            ksc = vsc = None
            new_cache = {"k": kc, "v": vc}
        if knobs.use_pallas:
            from repro.kernels import paged_decode_attention as _pallas_paged

            ctx = _pallas_paged(q, kc, vc, page_idx, pos, window=window,
                                k_scale=ksc, v_scale=vsc,
                                num_splits=knobs.decode_splits if t == 1
                                else 1)
        else:
            ctx = attn.paged_decode_attention_xla(q, kc, vc, page_idx, pos,
                                                  window=window, k_scale=ksc,
                                                  v_scale=vsc)
    else:
        upd = attn.cache_update_multi if t > 1 else attn.cache_update
        kc, vc = upd(cache["k"], cache["v"], k_new, v_new, pos)
        new_cache = {"k": kc, "v": vc}
        if knobs.use_pallas:
            from repro.kernels import decode_attention as _pallas_decode

            blk = min(512, kc.shape[1])
            ctx = _pallas_decode(q, kc, vc, pos, window=window, block_k=blk,
                                 num_splits=knobs.decode_splits)
        else:
            ctx = attn.decode_attention_xla(q, kc, vc, pos, window=window)
    ctx = shard_fn("attn_out", ctx)
    return x + attn.attn_output(p["attn"], ctx), new_cache


def _apply_attn_block_prefill_chunk(p, x, cache, slot, offset, *, cfg, window,
                                    knobs, ffn, shard_fn, paged=None,
                                    gather=False):
    x, new_cache = _attn_prefill_chunk(
        p, x, cache, slot, offset, cfg=cfg, window=window, knobs=knobs,
        shard_fn=shard_fn, paged=paged, gather=gather)
    return _ffn_out(p, x, ffn, cfg=cfg, shard_fn=shard_fn), new_cache


@jax.named_scope("attention")
def _attn_prefill_chunk(p, x, cache, slot, offset, *, cfg, window, knobs,
                        shard_fn, paged=None, gather=False):
    """One slot's prompt chunk: x (1,C,dm) at absolute positions
    offset..offset+C-1.  Writes the chunk's K/V into cache[slot] in place,
    then runs blocked flash attention of the chunk against the slot's full
    prefix (stale cache beyond offset+C is causally masked).

    ``paged = (page_idx, page_size)``: the chunk (C a page multiple,
    offset page-aligned) lands in the physical pages the slot's table
    maps, and the prefix is read back through the same indirection:

    * ``knobs.use_pallas`` — the fused paged prefill kernel reads K/V
      through the page table directly; no dense per-slot copy exists.
    * XLA with ``gk``/``gv`` leaves in ``cache`` — a dense (1, S, KV, D)
      per-slot gather *buffer* carried across chunks (zipped in by
      ``zip_prefill_buf``): chunk 0 of a prefix-cache hit re-gathers it
      once (``gather=True``); every other chunk just inserts its own
      fresh K/V, so the old per-chunk full-length gather is gone.
    * XLA without a buffer — the legacy full gather per chunk, kept as
      the parity oracle for both fast paths.
    """
    c = x.shape[1]
    h = rmsnorm(p["ln1"], x)
    positions = offset + jnp.arange(c)[None, :]
    q, k_new, v_new = attn.qkv_project(p["attn"], h, positions, cfg.rope_theta)
    if paged is not None:
        page_idx, page_size = paged
        quant = "k_scale" in cache
        if quant:
            kc, vc, ksc, vsc = attn.paged_prefill_chunk_update_quant(
                cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
                k_new, v_new, slot, offset, page_idx, page_size)
            new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
        else:
            kc, vc = attn.paged_prefill_chunk_update(
                cache["k"], cache["v"], k_new, v_new, slot, offset, page_idx,
                page_size)
            ksc = vsc = None
            new_cache = {"k": kc, "v": vc}
        if knobs.use_pallas:
            from repro.kernels import paged_prefill_attention as _pallas_pf

            if "gk" in cache:  # buffer unused on the fused path
                new_cache["gk"], new_cache["gv"] = cache["gk"], cache["gv"]
            ctx = _pallas_pf(q, kc, vc, page_idx, slot, offset,
                             window=window, k_scale=ksc, v_scale=vsc)
        else:
            if "gk" in cache:
                if gather:  # first chunk of a prefix hit: rebuild the view
                    gk, gv = attn.gather_slot_pages(kc, vc, page_idx, slot,
                                                    k_scale=ksc, v_scale=vsc)
                    gk = gk.astype(cache["gk"].dtype)
                    gv = gv.astype(cache["gv"].dtype)
                else:  # steady state: insert only this chunk's fresh K/V
                    if quant:  # round-trip so the buffer holds exactly
                        # what a page gather would return
                        k_ins = attn.dequantize_kv(
                            *attn.quantize_kv(k_new, kc.dtype))
                        v_ins = attn.dequantize_kv(
                            *attn.quantize_kv(v_new, vc.dtype))
                    else:
                        k_ins, v_ins = k_new, v_new
                    gk = jax.lax.dynamic_update_slice(
                        cache["gk"], k_ins.astype(cache["gk"].dtype),
                        (0, offset, 0, 0))
                    gv = jax.lax.dynamic_update_slice(
                        cache["gv"], v_ins.astype(cache["gv"].dtype),
                        (0, offset, 0, 0))
                new_cache["gk"], new_cache["gv"] = gk, gv
                k_slot, v_slot = gk, gv
            else:
                k_slot, v_slot = attn.gather_slot_pages(
                    kc, vc, page_idx, slot, k_scale=ksc, v_scale=vsc)
            ctx = attn.flash_attention_xla(q, k_slot, v_slot, causal=True,
                                           window=window,
                                           q_chunk=min(knobs.q_chunk, c),
                                           q_offset=offset)
    else:
        with jax.named_scope("kv_write"):
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k_new.astype(cache["k"].dtype),
                (slot, offset, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v_new.astype(cache["v"].dtype),
                (slot, offset, 0, 0))
        new_cache = {"k": kc, "v": vc}
        k_slot = jax.lax.dynamic_slice_in_dim(kc, slot, 1, axis=0)
        v_slot = jax.lax.dynamic_slice_in_dim(vc, slot, 1, axis=0)
        ctx = attn.flash_attention_xla(q, k_slot, v_slot, causal=True,
                                       window=window,
                                       q_chunk=min(knobs.q_chunk, c),
                                       q_offset=offset)
    ctx = shard_fn("attn_out", ctx)
    return x + attn.attn_output(p["attn"], ctx), new_cache


def _apply_ssm_block(p, x, *, cfg, collect_cache, shard_fn,
                     use_pallas=False):
    h = rmsnorm(p["ln"], x)
    if collect_cache:
        y, state = ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm,
                               return_state=True, use_pallas=use_pallas)
    else:
        y = ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm,
                        use_pallas=use_pallas)
        state = None
    x = shard_fn("hidden", x + y)
    return x, {}, state


def _apply_ssm_block_decode(p, x, cache, *, cfg, shard_fn):
    h = rmsnorm(p["ln"], x)
    y, new_cache = ssm_decode_step(p["ssm"], cache, h, cfg.d_model, cfg.ssm)
    return x + y, new_cache


# ========================================================== sequence apply
def apply_blocks(blocks, x, positions, *, cfg, knobs, mode: str):
    """mode: 'train' (no caches) | 'prefill' (emit caches).

    Returns (x, aux, caches_or_None).
    """
    plan = build_plan(cfg)
    ffn = _ffn_kind(cfg)
    shard_fn = knobs.shard_fn
    collect = mode == "prefill"
    remat = knobs.remat and mode == "train"

    def inner_body(p, xx, window):
        if plan.inner_kind == "attn":
            return _apply_attn_block(p, xx, positions, cfg=cfg, window=window,
                                     knobs=knobs, collect_cache=collect,
                                     ffn=ffn, shard_fn=shard_fn)
        return _apply_ssm_block(p, xx, cfg=cfg, collect_cache=collect,
                                shard_fn=shard_fn,
                                use_pallas=knobs.use_pallas)

    def outer_body(p, xx):
        return _apply_attn_block(
            p, xx, positions, cfg=cfg, window=plan.outer_window, knobs=knobs,
            collect_cache=collect, ffn="mlp" if plan.outer_shared else ffn,
            shard_fn=shard_fn)

    def scan_stack(stack, carry, window):
        def body(c, p):
            xx, aux = c
            xx, a, cache = inner_body(p, xx, window)
            return (xx, _acc_aux(aux, a)), cache
        if remat:
            body = jax.checkpoint(body)
        return jax.lax.scan(body, carry, stack)

    carry = (x, _zero_aux(cfg))
    if plan.kind == "uniform":
        carry, caches = scan_stack(blocks["stack"], carry, plan.inner_window)
        x, aux = carry
        return x, aux, ({"stack": caches} if collect else None)

    # grouped
    def group_body(c, xs):
        inner_stack = xs["inner"]
        c, inner_caches = scan_stack(inner_stack, c, plan.inner_window)
        xx, aux = c
        op = blocks["outer"] if plan.outer_shared else xs["outer"]
        xx, a, ocache = outer_body(op, xx)
        return (xx, _acc_aux(aux, a)), {"inner": inner_caches, "outer": ocache}

    if remat:
        group_body = jax.checkpoint(group_body)
    xs = {"inner": blocks["inner"]}
    if not plan.outer_shared:
        xs["outer"] = blocks["outer"]
    carry, gcaches = jax.lax.scan(group_body, carry, xs)
    if plan.remainder:
        carry, rcaches = scan_stack(blocks["rem"], carry, plan.inner_window)
    x, aux = carry
    if not collect:
        return x, aux, None
    caches = {"groups": gcaches}
    if plan.remainder:
        caches["rem"] = rcaches
    return x, aux, caches


# ============================================================ decode apply
def _walk_plan_cached(blocks, x, caches, *, cfg, inner_fn, outer_fn):
    """Shared plan walker for the cached paths (decode and chunked
    prefill): thread x and per-layer caches through the plan's stacks.

    inner_fn(p, x, cache, window) and outer_fn(p, x, cache, window, ffn)
    each return (x, new_cache); ffn is pre-resolved ("mlp" for shared
    outer blocks).
    """
    plan = build_plan(cfg)
    ffn = _ffn_kind(cfg)

    # the scan slices each layer's parameters and KV pools out of the
    # stacks and writes the layer's pools back: ops of the ``layer_scan``
    # scope outside ``attention``/``mlp`` are that traffic
    @jax.named_scope("layer_scan")
    def scan_stack(stack, cstack, xx, window):
        def body(c, inp):
            p, cache = inp
            return inner_fn(p, c, cache, window)
        return jax.lax.scan(body, xx, (stack, cstack))

    if plan.kind == "uniform":
        x, new = scan_stack(blocks["stack"], caches["stack"], x,
                            plan.inner_window)
        return x, {"stack": new}

    def group_body(xx, inp):
        xs, gcache = inp
        xx, new_inner = scan_stack(xs["inner"], gcache["inner"], xx,
                                   plan.inner_window)
        op = blocks["outer"] if plan.outer_shared else xs["outer"]
        xx, new_outer = outer_fn(op, xx, gcache["outer"], plan.outer_window,
                                 "mlp" if plan.outer_shared else ffn)
        return xx, {"inner": new_inner, "outer": new_outer}

    xs = {"inner": blocks["inner"]}
    if not plan.outer_shared:
        xs["outer"] = blocks["outer"]
    with jax.named_scope("layer_scan"):
        x, new_g = jax.lax.scan(group_body, x, (xs, caches["groups"]))
    new_caches = {"groups": new_g}
    if plan.remainder:
        x, new_rem = scan_stack(blocks["rem"], caches["rem"], x,
                                plan.inner_window)
        new_caches["rem"] = new_rem
    return x, new_caches


def apply_blocks_decode(blocks, x, caches, pos, *, cfg, knobs, paged=None):
    plan = build_plan(cfg)
    ffn = _ffn_kind(cfg)
    shard_fn = knobs.shard_fn
    if paged is not None and plan.inner_kind != "attn":
        raise NotImplementedError(
            f"paged KV cache unsupported for family={cfg.family!r}")
    if x.shape[1] > 1 and plan.inner_kind != "attn":
        raise NotImplementedError(
            f"multi-token (speculative) decode unsupported for "
            f"family={cfg.family!r} — SSM state advances one token at a "
            f"time")

    def inner_fn(p, xx, cache, window):
        if plan.inner_kind == "attn":
            return _apply_attn_block_decode(p, xx, cache, pos, cfg=cfg,
                                            window=window, knobs=knobs,
                                            ffn=ffn, shard_fn=shard_fn,
                                            paged=paged)
        return _apply_ssm_block_decode(p, xx, cache, cfg=cfg,
                                       shard_fn=shard_fn)

    def outer_fn(p, xx, cache, window, offn):
        return _apply_attn_block_decode(p, xx, cache, pos, cfg=cfg,
                                        window=window, knobs=knobs, ffn=offn,
                                        shard_fn=shard_fn, paged=paged)

    return _walk_plan_cached(blocks, x, caches, cfg=cfg, inner_fn=inner_fn,
                             outer_fn=outer_fn)


# ==================================================== chunked prefill apply
def supports_chunked_prefill(cfg) -> bool:
    """Chunked prefill needs every layer's prefix state to be recoverable
    from the KV cache alone; SSM/hybrid plans carry conv + SSD state across
    chunk boundaries and fall back to token feeding."""
    return build_plan(cfg).inner_kind == "attn"


def supports_paged_cache(cfg) -> bool:
    """Paged KV needs every cached layer to BE a KV cache; SSM/hybrid
    recurrent state is per-slot and position-free, so it cannot be paged."""
    return build_plan(cfg).inner_kind == "attn"


def supports_speculative(cfg) -> bool:
    """Speculative (multi-token) decode scores a whole draft block in one
    forward pass, which needs position-indexed caches only; SSM/hybrid
    recurrent state advances strictly one token at a time."""
    return build_plan(cfg).inner_kind == "attn"


def apply_blocks_prefill_chunk(blocks, x, caches, slot, offset, *, cfg,
                               knobs, paged=None, gather=False):
    """Run ONE slot's prompt chunk x (1,C,dm) through all layers, writing
    each layer's K/V into ``caches`` at (slot, offset) in place.  Returns
    (hidden (1,C,dm), new caches).  Attention-only plans.

    ``gather`` (paged XLA path with a zipped-in gather buffer only):
    re-initialize each layer's dense slot view from the page table before
    attending — the first chunk of a prefix-cache hit, where pages below
    ``offset`` were adopted rather than written by this prefill."""
    plan = build_plan(cfg)
    if plan.inner_kind != "attn":
        raise NotImplementedError(
            f"chunked prefill unsupported for family={cfg.family!r}")
    ffn = _ffn_kind(cfg)
    shard_fn = knobs.shard_fn

    def inner_fn(p, xx, cache, window):
        return _apply_attn_block_prefill_chunk(
            p, xx, cache, slot, offset, cfg=cfg, window=window, knobs=knobs,
            ffn=ffn, shard_fn=shard_fn, paged=paged, gather=gather)

    def outer_fn(p, xx, cache, window, offn):
        return _apply_attn_block_prefill_chunk(
            p, xx, cache, slot, offset, cfg=cfg, window=window, knobs=knobs,
            ffn=offn, shard_fn=shard_fn, paged=paged, gather=gather)

    return _walk_plan_cached(blocks, x, caches, cfg=cfg, inner_fn=inner_fn,
                             outer_fn=outer_fn)


# ------------------------------------------------- prefill gather buffer
def zip_prefill_buf(caches, buf):
    """Merge a dense per-slot gather buffer (an ``init_cache(1, max_len)``
    tree) into a paged cache tree as ``gk``/``gv`` keys on every attn
    leaf dict, so the plan walker threads buffer and pools through the
    layer scan together.  The buffer is the chunked-prefill fix: one
    (1, S, KV, D) view per layer reused across chunks instead of a fresh
    full-length gather per chunk."""
    if isinstance(caches, dict) and "k" in caches \
            and not isinstance(caches["k"], dict):
        out = dict(caches)
        out["gk"] = buf["k"]
        out["gv"] = buf["v"]
        return out
    return {key: zip_prefill_buf(caches[key], buf[key]) for key in caches}


def unzip_prefill_buf(merged):
    """Inverse of ``zip_prefill_buf``: (paged caches, buffer tree)."""
    if isinstance(merged, dict) and "gk" in merged \
            and not isinstance(merged["gk"], dict):
        cache = {key: val for key, val in merged.items()
                 if key not in ("gk", "gv")}
        return cache, {"k": merged["gk"], "v": merged["gv"]}
    pairs = {key: unzip_prefill_buf(merged[key]) for key in merged}
    return ({key: c for key, (c, _) in pairs.items()},
            {key: b for key, (_, b) in pairs.items()})


# ============================================================== cache init
def init_cache(cfg, knobs, batch: int, max_len: int):
    plan = build_plan(cfg)

    def attn_cache():
        return {
            "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim),
                           knobs.cache_dtype),
            "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim),
                           knobs.cache_dtype),
        }

    def inner_cache():
        if plan.inner_kind == "attn":
            return attn_cache()
        return ssm_init_cache(batch, cfg.d_model, cfg.ssm, knobs.cache_dtype)

    def stack(n, fn):
        return jax.tree.map(
            lambda z: jnp.broadcast_to(z, (n,) + z.shape).copy() if n else z,
            fn())

    if plan.kind == "uniform":
        return {"stack": stack(plan.n_layers, inner_cache)}
    caches = {"groups": {
        "inner": stack(plan.n_groups,
                       lambda: stack(plan.inner_per_group, inner_cache)),
        "outer": stack(plan.n_groups, attn_cache),
    }}
    if plan.remainder:
        caches["rem"] = stack(plan.remainder, inner_cache)
    return caches


def init_cache_paged(cfg, knobs, num_pages: int, page_size: int):
    """Paged KV pools: same plan tree as ``init_cache``, but every attn
    leaf is a global (num_pages, page_size, KV, D) pool shared by all
    slots instead of a per-slot (batch, max_len) stripe.  One page table
    addresses every layer — the stacked layer axes mean a (page, offset)
    coordinate is valid in each pool.

    ``knobs.kv_quant`` ("int8"/"fp8") stores quantized pools plus
    per-token/per-head scale leaves ``k_scale``/``v_scale``
    (num_pages, page_size, KV, 1) f32.  Scales keep the page axis at
    ndim-4 like every other paged leaf, so ``copy_cache_pages`` /
    ``copy_cache_pages_across`` move them with their pages automatically
    — CoW and disagg handoff need no special casing."""
    if not supports_paged_cache(cfg):
        raise NotImplementedError(
            f"paged KV cache unsupported for family={cfg.family!r}")
    plan = build_plan(cfg)

    def attn_cache():
        dt = (attn.KV_QUANT_DTYPES[knobs.kv_quant] if knobs.kv_quant
              else knobs.cache_dtype)
        cache = {
            "k": jnp.zeros((num_pages, page_size, cfg.num_kv_heads,
                            cfg.head_dim), dt),
            "v": jnp.zeros((num_pages, page_size, cfg.num_kv_heads,
                            cfg.head_dim), dt),
        }
        if knobs.kv_quant:
            cache["k_scale"] = jnp.zeros(
                (num_pages, page_size, cfg.num_kv_heads, 1), jnp.float32)
            cache["v_scale"] = jnp.zeros(
                (num_pages, page_size, cfg.num_kv_heads, 1), jnp.float32)
        return cache

    def stack(n, fn):
        return jax.tree.map(
            lambda z: jnp.broadcast_to(z, (n,) + z.shape).copy() if n else z,
            fn())

    if plan.kind == "uniform":
        return {"stack": stack(plan.n_layers, attn_cache)}
    caches = {"groups": {
        "inner": stack(plan.n_groups,
                       lambda: stack(plan.inner_per_group, attn_cache)),
        "outer": stack(plan.n_groups, attn_cache),
    }}
    if plan.remainder:
        caches["rem"] = stack(plan.remainder, attn_cache)
    return caches


def cache_batch_axes(cfg, knobs, max_len: int):
    """Per-leaf batch-axis index of the dense cache tree, found by
    diffing abstract cache shapes for two batch sizes (leaf layouts vary:
    stacked layer axes lead, SSM leaves differ from KV).  Pure host
    bookkeeping — drives ``copy_cache_out/in`` and the engine's slot
    reset without hardcoding any layout."""
    s1 = jax.eval_shape(lambda: init_cache(cfg, knobs, 1, max_len))
    s2 = jax.eval_shape(lambda: init_cache(cfg, knobs, 2, max_len))
    return jax.tree.map(
        lambda a, b: next(i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                          if x != y), s1, s2)


def copy_cache_out(caches, slot, axes):
    """Slice one slot's stripe out of every dense cache leaf (keeping a
    size-1 batch dim) — the device half of a preemption checkpoint; the
    engine ``device_get``s the result to a host-side buffer.  ``axes`` is
    the ``cache_batch_axes`` tree."""
    return jax.tree.map(
        lambda c, ax: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=ax),
        caches, axes)


def copy_cache_in(caches, snapshot, slot, axes):
    """Write a ``copy_cache_out`` snapshot back into slot ``slot`` of
    every leaf — restore half of checkpoint/resume.  The full stripe is
    rewritten, so the slot's previous occupant leaves no residue and
    SSM/recurrent leaves restore exactly."""
    return jax.tree.map(
        lambda c, s, ax: jax.lax.dynamic_update_slice_in_dim(c, s, slot,
                                                             axis=ax),
        caches, snapshot, axes)


def copy_cache_pages(caches, src, dst):
    """Copy physical page ``src`` -> ``dst`` in every layer pool (the
    device half of copy-on-write).  The page axis of every paged leaf sits
    at ndim-4 — (..., num_pages, page_size, KV, D) under the stacked layer
    axes."""
    def cp(leaf):
        ax = leaf.ndim - 4
        page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=ax)
        return jax.lax.dynamic_update_slice_in_dim(leaf, page, dst, axis=ax)

    return jax.tree.map(cp, caches)


def copy_cache_pages_across(src_caches, dst_caches, src_idx, dst_idx):
    """Gather pages ``src_idx`` from one engine's paged pools and scatter
    them at ``dst_idx`` in another's — the device half of a cross-engine
    page-chain transfer (disaggregated prefill -> decode handoff).

    ``src_idx``/``dst_idx`` are equal-length int32 vectors; padding both
    with 0 makes the extra rows copy the source null page onto the
    destination null page, which no reader ever depends on, so the
    vectors can be padded to a static width and the copy compiles once
    per width.  Both trees must share the plan (same stacked layer axes)
    and page_size; pool sizes may differ."""
    def cp(s_leaf, d_leaf):
        ax = s_leaf.ndim - 4
        s0 = jnp.moveaxis(s_leaf, ax, 0)
        d0 = jnp.moveaxis(d_leaf, ax, 0)
        d0 = d0.at[dst_idx].set(s0[src_idx])
        return jnp.moveaxis(d0, 0, ax)

    return jax.tree.map(cp, src_caches, dst_caches)
