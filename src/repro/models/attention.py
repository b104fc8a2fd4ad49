"""Attention: GQA/MQA projections + blocked (flash-style) XLA attention.

Three execution paths:

* ``flash_attention_xla`` — training/prefill.  Scans over query chunks with a
  transient (B, heads, q_chunk, kv_len) score tile, so the full (S, S) score
  matrix is never materialized (the XLA analogue of flash attention; the
  Pallas TPU kernel in ``repro.kernels`` implements the same contract).
  For windowed layers (SWA / gemma3-local) the KV is *dynamically sliced* to
  the window, making the HLO FLOPs genuinely sub-quadratic.
* ``decode_attention_xla`` — one query token against a KV cache (O(S)).
* ``repro.kernels.ops`` — Pallas kernels selected with ``use_pallas`` on TPU.

Weights layout: wq (dm, H, hd), wk/wv (dm, KV, hd), wo (H, hd, dm) so that the
head axes are explicit for sharding rules.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import _init, apply_rope


# ------------------------------------------------------------------ params
def attention_init(key, *, d_model, num_heads, num_kv_heads, head_dim, qkv_bias,
                   dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "wq": _init(ks[0], (d_model, num_heads, head_dim), dtype=dtype),
        "wk": _init(ks[1], (d_model, num_kv_heads, head_dim), dtype=dtype),
        "wv": _init(ks[2], (d_model, num_kv_heads, head_dim), dtype=dtype),
        "wo": _init(ks[3], (num_heads, head_dim, d_model), dtype=dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads, head_dim), dtype=dtype)
        p["bk"] = jnp.zeros((num_kv_heads, head_dim), dtype=dtype)
        p["bv"] = jnp.zeros((num_kv_heads, head_dim), dtype=dtype)
    return p


def qkv_project(params, x, positions, rope_theta):
    """x (B,S,dm) -> q (B,S,H,hd), k,v (B,S,KV,hd) with RoPE applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def attn_output(params, ctx):
    return jnp.einsum("bshk,hkd->bsd", ctx, params["wo"])


# ------------------------------------------------------- grouped attention
def _grouped_scores(q, k):
    """q (B,bq,KV,G,D), k (B,Sk,KV,D) -> scores (B,KV,G,bq,Sk) fp32."""
    scale = q.shape[-1] ** -0.5
    return jnp.einsum("bqhgd,bshd->bhgqs", q, k).astype(jnp.float32) * scale


def _grouped_context(probs, v):
    """probs (B,KV,G,bq,Sk) fp32, v (B,Sk,KV,D) -> (B,bq,KV,G,D)."""
    return jnp.einsum("bhgqs,bshd->bqhgd", probs.astype(v.dtype), v)


def flash_attention_xla(q, k, v, *, causal=True, window=0, q_chunk=512,
                        q_offset=0, causal_skip=False):
    """Blocked attention.  q (B,Sq,H,D); k,v (B,Sk,KV,D); GQA-aware.

    window > 0 -> sliding-window attention: each query chunk only reads the
    (window + q_chunk)-long KV slice it can see, so compiled FLOPs scale with
    S * window rather than S^2.

    causal_skip -> recursive triangle decomposition: the upper query half
    attends the full prefix, the lower half recurses on the shorter prefix.
    All slice lengths are static; compiled FLOPs drop to ~0.67x of the
    full-rectangle baseline (ideal causal = 0.5x) with only ~depth extra
    HLO bodies (EXPERIMENTS.md §Perf H2).
    """
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    q_chunk = min(q_chunk, sq)
    assert sq % q_chunk == 0, (sq, q_chunk)
    nq = sq // q_chunk

    if causal_skip and causal and not window and q_offset + sq == sk:
        return _flash_causal_recursive(q, k, v, q_chunk=q_chunk,
                                       q_offset=q_offset)

    qg = q.reshape(b, nq, q_chunk, kv, g, d).swapaxes(0, 1)  # (nq,B,bq,KV,G,D)
    kv_span = min(sk, window + q_chunk) if window else sk

    def body(_, inp):
        qc, idx = inp
        qs = idx * q_chunk + q_offset  # absolute position of first query
        qpos = qs + jnp.arange(q_chunk)
        if window and kv_span < sk:
            start = jnp.clip(qs + q_chunk - kv_span, 0, sk - kv_span)
            kc = jax.lax.dynamic_slice_in_dim(k, start, kv_span, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, start, kv_span, axis=1)
            kpos = start + jnp.arange(kv_span)
        else:
            kc, vc, kpos = k, v, jnp.arange(sk)
        scores = _grouped_scores(qc, kc)  # (B,KV,G,bq,span)
        mask = jnp.ones((q_chunk, kpos.shape[0]), dtype=bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _grouped_context(probs, vc)  # (B,bq,KV,G,D)
        return None, out

    # nested remat: without it the scan VJP stores fp32 probs for every
    # chunk — the full (S, S) attention matrix (flash backward instead
    # recomputes scores per chunk; measured 12 GB -> ~3 GB on qwen2.5 train)
    body = jax.checkpoint(body)
    _, chunks = jax.lax.scan(body, None, (qg, jnp.arange(nq)))
    out = chunks.swapaxes(0, 1).reshape(b, sq, h, d)
    return out


def _flash_causal_recursive(q, k, v, *, q_chunk, q_offset, depth=4):
    """Static triangle decomposition of causal attention.

    q (B, Sq, H, D) attends k[:, :q_offset+Sq] causally.  The upper half of
    the queries runs one rectangular blocked flash over the full prefix;
    the lower half recurses with a prefix half as long.  Cost ratio vs the
    full rectangle: r_d = 0.5 * (1 + 1/4 + ... ) -> ~0.67 at depth 4.
    """
    sq = q.shape[1]
    end = q_offset + sq
    half = (sq // 2 // q_chunk) * q_chunk
    if depth == 0 or half < q_chunk or sq <= 2 * q_chunk:
        return flash_attention_xla(q, k[:, :end], v[:, :end], causal=True,
                                   q_chunk=q_chunk, q_offset=q_offset)
    lower = _flash_causal_recursive(q[:, :half], k, v, q_chunk=q_chunk,
                                    q_offset=q_offset, depth=depth - 1)
    upper = flash_attention_xla(q[:, half:], k[:, :end], v[:, :end],
                                causal=True, q_chunk=q_chunk,
                                q_offset=q_offset + half)
    return jnp.concatenate([lower, upper], axis=1)


def decode_attention_xla(q, k_cache, v_cache, pos, *, window=0):
    """Decode-time attention.  q (B,T,H,D); caches (B,S,KV,D).

    Reads the whole cache (O(S)); positions beyond ``pos`` and outside the
    window are masked.  Ragged: ``pos`` may be a scalar (lockstep) or a
    (B,) vector of per-slot prefix lengths — the XLA mirror of the Pallas
    per-slot kernel contract.  Slots with pos < 0 are inactive and return
    zeros.

    T > 1 is the speculative multi-token verify block: query row ``t``
    of slot ``b`` sits at absolute position ``pos[b] + t`` and attends
    keys ``kpos <= pos[b] + t`` — causal against the prefix AND within
    the draft (row t sees draft rows 0..t, freshly written to the cache
    before this call).  T = 1 is the classic one-token decode step.
    """
    b, t, h, d = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    qpos = pos[:, None] + jnp.arange(t)[None, :]  # (B, T)
    qg = q.reshape(b, t, kv, g, d)
    scores = _grouped_scores(qg, k_cache)  # (B,KV,G,T,S)
    kpos = jnp.arange(s)
    mask = kpos[None, None, :] <= qpos[:, :, None]  # (B, T, S)
    if window:
        mask &= qpos[:, :, None] - kpos[None, None, :] < window
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _grouped_context(probs, v_cache)  # (B,T,KV,G,D)
    out = jnp.where((pos >= 0)[:, None, None, None, None], out, 0.0)
    return out.reshape(b, t, h, d).astype(q.dtype)


def paged_decode_attention_xla(q, k_pages, v_pages, page_idx, pos, *,
                               window=0, k_scale=None, v_scale=None):
    """Paged decode attention, XLA reference path.

    q (B,T,H,D); pools (P, page_size, KV, D); page_idx (B, max_pages)
    int32 (0 = null page for unmapped blocks).  Gathers each slot's pages
    into a dense (B, S, KV, D) view and defers to
    ``decode_attention_xla`` (T > 1 = the speculative verify block) — the
    Pallas kernel resolves the same indirection inside its
    scalar-prefetched index_map instead of materializing the gather.

    ``k_scale``/``v_scale`` (P, page_size, KV, 1) f32 select the
    quantized-pool path: the gathered int8/fp8 values are dequantized
    with their per-token scales (the XLA mirror of the kernel's in-VMEM
    dequant).
    """
    b = q.shape[0]
    _, page_size, kv, d = k_pages.shape
    max_pages = page_idx.shape[1]
    s = max_pages * page_size
    idx = jnp.asarray(page_idx, jnp.int32)
    k = jnp.take(k_pages, idx, axis=0).reshape(b, s, kv, d)
    v = jnp.take(v_pages, idx, axis=0).reshape(b, s, kv, d)
    if k_scale is not None:
        k = k.astype(jnp.float32) * jnp.take(k_scale, idx,
                                             axis=0).reshape(b, s, kv, 1)
        v = v.astype(jnp.float32) * jnp.take(v_scale, idx,
                                             axis=0).reshape(b, s, kv, 1)
    return decode_attention_xla(q, k, v, pos, window=window)


# ------------------------------------------------------------ quantized KV
KV_QUANT_DTYPES = {"int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
_KV_QUANT_QMAX = {"int8": 127.0, "fp8": 448.0}  # e4m3fn finite max


def kv_quant_dtype(kv_quant: str):
    """Pool dtype for a ``RuntimeKnobs.kv_quant`` mode string ("" — the
    unquantized default — maps to None: store at cache_dtype)."""
    return KV_QUANT_DTYPES[kv_quant] if kv_quant else None


def quantize_kv(x, qdtype):
    """Per-token/per-head symmetric quantization of fresh K/V rows.

    x (..., D) fp -> (q (..., D) ``qdtype``, scale (..., 1) f32) with
    scale = absmax / qmax over the head dim.  All-zero rows get scale 0
    (dequant is exactly zero); dequant is ``q.astype(f32) * scale``.
    """
    qmax = {jnp.dtype(d): m for d, m in
            ((KV_QUANT_DTYPES[k], _KV_QUANT_QMAX[k]) for k in
             KV_QUANT_DTYPES)}[jnp.dtype(qdtype)]
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = amax / qmax
    inv = jnp.where(amax > 0, 1.0 / jnp.maximum(scale, 1e-30), 0.0)
    if jnp.dtype(qdtype) == jnp.dtype(jnp.int8):
        q = jnp.clip(jnp.round(xf * inv), -qmax, qmax).astype(jnp.int8)
    else:
        q = (xf * inv).astype(qdtype)
    return q, scale


def dequantize_kv(q, scale):
    """Inverse of ``quantize_kv``: (..., D) quantized + (..., 1) f32."""
    return q.astype(jnp.float32) * scale


@jax.named_scope("kv_write")
def paged_cache_update_quant(k_pages, v_pages, k_scale, v_scale, k_new,
                             v_new, pos, page_idx, page_size):
    """Quantized ``paged_cache_update``: quantize the fresh (B,1,KV,D)
    rows per-token/per-head, scatter values into the int8/fp8 pools and
    scales into the (P, page_size, KV, 1) f32 scale pools through the
    same page-table indirection.  Every write is incremental — no
    read-modify-requantize of existing pages, so quant error never
    accumulates."""
    kq, ks = quantize_kv(k_new, k_pages.dtype)
    vq, vs = quantize_kv(v_new, v_pages.dtype)
    k_pages, v_pages = paged_cache_update(k_pages, v_pages, kq, vq, pos,
                                          page_idx, page_size)
    k_scale, v_scale = paged_cache_update(k_scale, v_scale, ks, vs, pos,
                                          page_idx, page_size)
    return k_pages, v_pages, k_scale, v_scale


@jax.named_scope("kv_write")
def paged_prefill_chunk_update_quant(k_pages, v_pages, k_scale, v_scale,
                                     k_new, v_new, slot, offset, page_idx,
                                     page_size):
    """Quantized ``paged_prefill_chunk_update`` (same delegation shape as
    ``paged_cache_update_quant``)."""
    kq, ks = quantize_kv(k_new, k_pages.dtype)
    vq, vs = quantize_kv(v_new, v_pages.dtype)
    k_pages, v_pages = paged_prefill_chunk_update(
        k_pages, v_pages, kq, vq, slot, offset, page_idx, page_size)
    k_scale, v_scale = paged_prefill_chunk_update(
        k_scale, v_scale, ks, vs, slot, offset, page_idx, page_size)
    return k_pages, v_pages, k_scale, v_scale


@jax.named_scope("kv_write")
def paged_cache_update_multi_quant(k_pages, v_pages, k_scale, v_scale,
                                   k_new, v_new, pos, page_idx, page_size):
    """Quantized ``paged_cache_update_multi`` (speculative verify
    blocks)."""
    kq, ks = quantize_kv(k_new, k_pages.dtype)
    vq, vs = quantize_kv(v_new, v_pages.dtype)
    k_pages, v_pages = paged_cache_update_multi(
        k_pages, v_pages, kq, vq, pos, page_idx, page_size)
    k_scale, v_scale = paged_cache_update_multi(
        k_scale, v_scale, ks, vs, pos, page_idx, page_size)
    return k_pages, v_pages, k_scale, v_scale


@jax.named_scope("kv_write")
def paged_cache_update(k_pages, v_pages, k_new, v_new, pos, page_idx,
                       page_size):
    """Insert (B,1,KV,D) at logical position ``pos`` through the page
    table: slot ``b`` writes physical page ``page_idx[b, pos[b] //
    page_size]`` at offset ``pos[b] % page_size``.

    Inactive slots (pos < 0) write the null page (physical page 0, never
    mapped), so the scatter needs no branch; its contents are don't-care.
    """
    b = k_new.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    idx = jnp.asarray(page_idx, jnp.int32)
    posc = jnp.maximum(pos, 0)
    blk = posc // page_size
    off = posc % page_size
    page = jnp.take_along_axis(idx, blk[:, None], axis=1)[:, 0]
    page = jnp.where(pos >= 0, page, 0)
    k_pages = k_pages.at[page, off].set(k_new[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[page, off].set(v_new[:, 0].astype(v_pages.dtype))
    return k_pages, v_pages


@jax.named_scope("kv_write")
def paged_prefill_chunk_update(k_pages, v_pages, k_new, v_new, slot, offset,
                               page_idx, page_size):
    """Write one slot's prompt chunk (1, C, KV, D), C a multiple of
    ``page_size`` and ``offset`` page-aligned, into the C // page_size
    physical pages its page-table row maps at block ``offset //
    page_size``."""
    c = k_new.shape[1]
    assert c % page_size == 0, (c, page_size)
    m = c // page_size
    kv, d = k_new.shape[2], k_new.shape[3]
    idx = jnp.asarray(page_idx, jnp.int32)
    pages = jax.lax.dynamic_slice(idx, (slot, offset // page_size),
                                  (1, m))[0]
    k_pages = k_pages.at[pages].set(
        k_new.reshape(m, page_size, kv, d).astype(k_pages.dtype))
    v_pages = v_pages.at[pages].set(
        v_new.reshape(m, page_size, kv, d).astype(v_pages.dtype))
    return k_pages, v_pages


def gather_slot_pages(k_pages, v_pages, page_idx, slot, k_scale=None,
                      v_scale=None):
    """Dense (1, S, KV, D) view of one slot's mapped prefix (chunked
    prefill reads through this; unmapped blocks gather the null page and
    are causally masked).  With ``k_scale``/``v_scale`` the quantized
    pools are gathered *and dequantized* — the view is fp32."""
    _, page_size, kv, d = k_pages.shape
    max_pages = page_idx.shape[1]
    s = max_pages * page_size
    idx = jnp.asarray(page_idx, jnp.int32)
    row = jax.lax.dynamic_slice(idx, (slot, 0), (1, max_pages))[0]
    k = jnp.take(k_pages, row, axis=0).reshape(1, s, kv, d)
    v = jnp.take(v_pages, row, axis=0).reshape(1, s, kv, d)
    if k_scale is not None:
        k = k.astype(jnp.float32) * jnp.take(k_scale, row,
                                             axis=0).reshape(1, s, kv, 1)
        v = v.astype(jnp.float32) * jnp.take(v_scale, row,
                                             axis=0).reshape(1, s, kv, 1)
    return k, v


@jax.named_scope("kv_write")
def paged_cache_update_multi(k_pages, v_pages, k_new, v_new, pos, page_idx,
                             page_size):
    """Insert a (B,T,KV,D) draft block at logical positions ``pos[b] + t``
    through the page table — the multi-token (speculative verify)
    ``paged_cache_update``.

    Page-aware write contract: token ``t`` of slot ``b`` lands in page
    ``page_idx[b, (pos[b]+t) // page_size]``.  Inactive slots (pos < 0)
    and positions past the table's logical span write the null page
    (entry 0), so draft padding beyond a slot's reservation can never
    clobber live data or touch an unheld page — rollback of rejected
    tokens is pure position truncation, no page ever changes hands.

    One scatter per pool (indices (B, T)) rather than T single-token
    scatters: XLA CPU pays ~100us per scatter op, which at draft depths
    of 4+ would eat the ticks speculation saves.
    """
    b, t = k_new.shape[0], k_new.shape[1]
    idx = jnp.asarray(page_idx, jnp.int32)
    max_len = idx.shape[1] * page_size
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    pos_t = pos[:, None] + jnp.arange(t)[None, :]  # (B, T) logical
    valid = (pos[:, None] >= 0) & (pos_t < max_len)
    posc = jnp.clip(pos_t, 0, max_len - 1)
    blk = posc // page_size
    off = posc % page_size
    page = jnp.take_along_axis(idx, blk, axis=1)  # (B, T) physical
    page = jnp.where(valid, page, 0)  # null page for don't-care rows
    k_pages = k_pages.at[page, off].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[page, off].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


@jax.named_scope("kv_write")
def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Insert (B,1,KV,D) at position ``pos`` of (B,S,KV,D) caches.

    ``pos`` scalar writes all slots at one position (lockstep decode); a
    (B,) vector writes each slot at its own position (ragged decode).
    Negative positions clamp to 0 — an inactive slot's garbage write lands
    at index 0 and is overwritten when the slot is next admitted.
    """
    pos = jnp.asarray(pos, jnp.int32)
    k_new = k_new.astype(k_cache.dtype)
    v_new = v_new.astype(v_cache.dtype)
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, pos,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, pos,
                                                      axis=1)
        return k_cache, v_cache
    upd = jax.vmap(
        lambda c, n, p: jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0))
    return upd(k_cache, k_new, pos), upd(v_cache, v_new, pos)


@jax.named_scope("kv_write")
def cache_update_multi(k_cache, v_cache, k_new, v_new, pos):
    """Insert a (B,T,KV,D) draft block at positions ``pos[b] + t`` of
    (B,S,KV,D) caches — the multi-token ``cache_update``.

    One scatter per cache with explicit (B, T) row indices rather than a
    length-T ``dynamic_update_slice`` block (which clamps the block so it
    *fits*, silently shifting a draft straddling the cache end onto
    earlier live positions) or T single-token scatters (XLA CPU pays
    ~100us per scatter op).  Each overflowing position clamps to S-1
    individually — the engine never lets an *accepted* token land there,
    so the clamped writes are draft padding whose garbage is never
    attended (rollback = position truncation); inactive slots (pos < 0)
    clamp to the don't-care low positions exactly like the single-token
    path.
    """
    t = k_new.shape[1]
    b, s = k_cache.shape[0], k_cache.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    rows = jnp.clip(pos[:, None] + jnp.arange(t)[None, :], 0, s - 1)
    bidx = jnp.arange(b)[:, None]
    k_cache = k_cache.at[bidx, rows].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, rows].set(v_new.astype(v_cache.dtype))
    return k_cache, v_cache
