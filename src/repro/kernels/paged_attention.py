"""Pallas TPU paged kernels: flash-decode, fused prefill, split-K decode.

The dense ragged kernel (``decode_attention.py``) streams a per-slot
``(max_len)`` KV stripe; these kernels stream only the pages a slot's page
table maps.  K/V live in a global pool ``(P, KV, page_size, D)`` shared by
every slot; ``page_idx (B, max_pages)`` rides the same scalar-prefetch
channel as ``pos (B,)`` / ``active (B,)``, so the kernels resolve the
indirection themselves and no materialized (B, S) copy of the cache ever
exists.

Decode (``paged_decode_attention_tpu``, and phase 1 of
``paged_decode_attention_splitk_tpu``) runs one program per slot and
split, grid ``(B, num_splits)``:

* The program's q block is the slot's whole ``(H, T, D)``, grouped as
  ``(KV, g * T, D)`` with ``g = H // KV``, so one page of a KV head meets
  all of that head's query rows in one batched matmul over the KV axis.
* The pools stay in HBM (``pl.ANY``).  The program reads only the logical
  pages ``first..last`` its query rows attend: ``last = (pos + T - 1) //
  page_size``, ``first = max(0, pos - window + 1) // page_size`` when
  windowed (else 0), both clipped to its split's page range.  Each page is
  one ``make_async_copy`` of physical page ``page_idx[b, i]`` for all KV
  heads at once (contiguous in the kernel layout), read once.  An inactive
  slot reads nothing and writes zeros.
* Copies go in blocks of about 128 keys (``_BLOCK_KEYS // page_size``
  pages), double-buffered: block ``i + 1``'s copies are in flight while
  block ``i`` is computed; the last block may hold fewer pages.  Within a
  block the online-softmax update runs page by page in logical order, the
  same arithmetic as the prefill kernel's per-page step.
* Split-K splits are page ranges (``max_pages % num_splits == 0``; see
  ``pick_decode_splits``); each program emits its unnormalized partial and
  the dense combine kernel merges them.

``paged_prefill_attention_tpu`` — one slot's prefill *chunk* (C query rows
at absolute offset ``q_offset``) against its own page chain — keeps the
BlockSpec form: grid step ``(h, ip)`` DMAs physical page ``page_row[ip]``
through the index_map.  This replaces the XLA path's dense per-slot gather:
chunked prefill never materializes a (max_len) copy of the cache.

Quantized pools: every variant accepts optional per-token/per-head scale
pools ``(P, KV, page_size, 1)`` f32 riding the same page indirection as
K/V.  Values are dequantized **inside** the kernel right after the copy
(``k * k_scale``), so int8/fp8 pools halve/quarter the HBM bytes per page
while the MXU math stays fp32.

Contract (a strict extension of the ragged dense kernel's):

* ``pos (B,)`` int32 (scalar broadcasts): slot ``b`` attends key positions
  ``kpos <= pos[b]`` (and ``pos[b] - kpos < window`` when windowed), where
  ``kpos = ip * page_size + offset`` is the *logical* position — page
  indirection never changes the mask math.
* ``active (B,)`` 0/1 (default ``pos >= 0``): inactive slots issue no DMA
  and no MXU work and write zeros.
* Unmapped page-table entries MUST be 0 (the pool's reserved null page).
  The decode kernels never read a page outside ``first..last``; the
  prefill kernel DMAs every entry of its row but computes only on the
  pages its rows attend, so their contents are don't-care.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import (NEG_INF, _block_needed, _normalize_pos,
                               _splitk_combine_kernel)


def _page_scale_spec(page_size, index_map):
    return pl.BlockSpec((1, 1, page_size, 1), index_map)


def _accumulate_page(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                     acc_ref, *, k_start, pos, window, scale, tq, page_size,
                     quant):
    """One online-softmax step over one page (the prefill kernel's).

    ``quant`` dequantizes K/V with the per-token scale blocks right after
    the VMEM load; fp math is otherwise identical to the unquantized path.
    """
    q = q_ref[0, 0].astype(jnp.float32)  # (tq, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (page_size, D)
    v = v_ref[0, 0]
    if quant:
        k = k * ks_ref[0, 0]                       # (page_size, D) * (ps, 1)
        v = v.astype(jnp.float32) * vs_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (tq, page_size), 1)
    qpos = pos + jax.lax.broadcasted_iota(jnp.int32, (tq, page_size), 0)
    mask = kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    # mask-gated exp — see _decode_kernel: draft rows fully masked in
    # a needed page must contribute exactly zero
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new


# keys per DMA block: a whole number of pages, about one MXU width
_BLOCK_KEYS = 128


def _accumulate_kv_page(q, k, v, m_ref, l_ref, acc_ref, *, k_start, qpos,
                        window, scale):
    """One online-softmax step of every KV head over one page.

    ``_accumulate_page``'s math with a leading KV batch axis: q (KV, R, D)
    f32 holds each KV head's ``R = g * T`` query rows, k/v (KV, page_size,
    D) one page of every KV head, ``qpos`` (KV, R, page_size) each row's
    absolute position.
    """
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    mask = kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
    # mask-gated exp — see _decode_kernel: draft rows fully masked in
    # a needed page must contribute exactly zero
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=2, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v.dtype), v,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new


def _scale_rows(scale):
    """Scale pool (P, KV, page_size, 1) -> (P, 1, L): a page's scales as
    one lane row padded to a whole number of 128-lane tiles, the only
    shape a page's scales can be copied in (a copy's minor dimension is
    tiled by 128 lanes)."""
    n_pool, kv, page_size, _ = scale.shape
    row = scale.reshape(n_pool, 1, kv * page_size)
    return jnp.pad(row, ((0, 0), (0, 0), (0, -kv * page_size % 128)))


def _scale_column(row, kv, page_size):
    """(1, L) lane row of one page's scales -> (KV, page_size, 1), by way
    of a transpose (Mosaic reshapes no lanes into sublanes)."""
    col = jnp.broadcast_to(row, (8, row.shape[1])).T[:kv * page_size, :1]
    return col.reshape(kv, page_size, 1)


def _paged_decode_kernel(page_ref, pos_ref, act_ref, q_ref, k_hbm, v_hbm,
                         *rest, window: int, page_size: int, scale: float,
                         tq: int, pages_per_split: int, block_pages: int,
                         quant: bool, partial: bool):
    """Program ``(b, isp)``: slot ``b``'s attended pages inside split
    ``isp``'s page range, fetched in blocks of ``block_pages`` pages with
    the next block's DMA in flight while this one is computed."""
    n_scales, n_out = (2 if quant else 0), (3 if partial else 1)
    pools = (k_hbm, v_hbm) + rest[:n_scales]  # HBM, with their VMEM bufs
    outs = rest[n_scales:n_scales + n_out]
    bufs = rest[n_scales + n_out:-4]
    sem, m_ref, l_ref, acc_ref = rest[-4:]
    ib = pl.program_id(0)
    isp = pl.program_id(1)
    pos = pos_ref[ib]
    active = act_ref[ib]

    # logical pages [first, stop): the keys rows pos..pos+tq-1 attend,
    # clipped to this split's range; an inactive slot reads none
    lo = isp * pages_per_split
    first = lo
    if window:
        first = jnp.maximum(first, jax.lax.div(
            jnp.maximum(pos - window + 1, 0), page_size))
    stop = jnp.minimum(lo + pages_per_split, jax.lax.div(
        jnp.maximum(pos + tq, 0) + page_size - 1, page_size))
    n = jnp.where(active > 0, jnp.maximum(stop - first, 0), 0)
    n_blocks = jax.lax.div(n + block_pages - 1, block_pages)

    def copies(blk, slot, j):
        page = page_ref[ib, first + blk * block_pages + j]
        return [pltpu.make_async_copy(src.at[page], buf.at[slot, j],
                                      sem.at[slot])
                for src, buf in zip(pools, bufs)]

    def each_page(blk, fn):
        for j in range(block_pages):
            pl.when(blk * block_pages + j < n)(functools.partial(fn, j))

    def start(blk, slot):
        def go(j):
            for c in copies(blk, slot, j):
                c.start()
        each_page(blk, go)

    def wait(blk, slot):
        def go(j):
            for c in copies(blk, slot, j):
                c.wait()
        each_page(blk, go)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0].astype(jnp.float32)  # (KV, g * tq, D), rows (g, tq)
    qpos = pos + jax.lax.broadcasted_iota(
        jnp.int32, (q.shape[0], q.shape[1], page_size), 1) % tq

    pl.when(n > 0)(lambda: start(0, 0))

    def block(blk, carry):
        slot = blk % 2
        pl.when(blk + 1 < n_blocks)(lambda: start(blk + 1, 1 - slot))
        wait(blk, slot)

        def page(j):
            k = bufs[0][slot, j].astype(jnp.float32)  # (KV, page_size, D)
            v = bufs[1][slot, j]
            if quant:  # dequantize right after the copy
                kv = k.shape[0]
                k = k * _scale_column(bufs[2][slot, j], kv, page_size)
                v = v.astype(jnp.float32) * _scale_column(bufs[3][slot, j],
                                                          kv, page_size)
            k_start = (first + blk * block_pages + j) * page_size
            _accumulate_kv_page(q, k, v, m_ref, l_ref, acc_ref,
                                k_start=k_start, qpos=qpos, window=window,
                                scale=scale)

        each_page(blk, page)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    if partial:
        # unnormalized: combine phase rescales by exp(m_i - m*) / sum l
        o_ref, ms_ref, ls_ref = outs
        o_ref[0, 0] = acc_ref[...]
        ms_ref[0, 0] = m_ref[...]
        ls_ref[0, 0] = l_ref[...]
    else:
        denom = jnp.maximum(l_ref[...], 1e-30)
        outs[0][0] = (acc_ref[...] / denom).astype(outs[0].dtype)


def _paged_decode_call(q, k_pages, v_pages, page_idx, pos, active, *,
                       window, k_scale, v_scale, num_splits, interpret):
    """Run ``_paged_decode_kernel`` over grid ``(B, num_splits)``.

    Returns the output (B, KV, g * T, D) for one split, else the split
    partials (acc, m, l), each (B, num_splits, KV, g * T, D or 1) f32.
    """
    b, h, tq, d = q.shape
    _, kv, page_size, _ = k_pages.shape
    max_pages = page_idx.shape[1]
    assert page_idx.shape[0] == b, (page_idx.shape, b)
    assert max_pages % num_splits == 0, (
        "split count must divide max_pages so splits tile whole pages",
        max_pages, num_splits)
    quant = k_scale is not None
    rows = h // kv * tq
    pps = max_pages // num_splits
    block_pages = max(1, min(pps, _BLOCK_KEYS // page_size))
    partial = num_splits > 1
    pos = _normalize_pos(pos, b)
    if active is None:
        active = (pos >= 0).astype(jnp.int32)
    else:
        active = jnp.broadcast_to(
            jnp.asarray(active, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(
        _paged_decode_kernel, window=window, page_size=page_size,
        scale=d ** -0.5, tq=tq, pages_per_split=pps,
        block_pages=block_pages, quant=quant, partial=partial)
    pools = [k_pages, v_pages]
    if quant:
        pools += [_scale_rows(x) for x in (k_scale, v_scale)]
    in_specs = ([pl.BlockSpec((1, kv, rows, d),
                              lambda b_, s_, *_: (b_, 0, 0, 0))]
                + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools))
    if partial:
        shapes = [(b, num_splits, kv, rows, n) for n in (d, 1, 1)]
        out_shape = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
        out_specs = [pl.BlockSpec((1, 1) + s[2:],
                                  lambda b_, s_, *_: (b_, s_, 0, 0, 0))
                     for s in shapes]
    else:
        out_shape = jax.ShapeDtypeStruct((b, kv, rows, d), q.dtype)
        out_specs = pl.BlockSpec((1, kv, rows, d),
                                 lambda b_, s_, *_: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_idx, pos, active
        grid=(b, num_splits),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, block_pages) + x.shape[1:], x.dtype)
            for x in pools] + [
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((kv, rows, 1), jnp.float32),
            pltpu.VMEM((kv, rows, 1), jnp.float32),
            pltpu.VMEM((kv, rows, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.asarray(page_idx, jnp.int32), pos, active,
      q.reshape(b, kv, rows, d), *pools)


def paged_decode_attention_tpu(q, k_pages, v_pages, page_idx, pos, *,
                               active=None, window=0, k_scale=None,
                               v_scale=None, interpret=False):
    """q (B, H, T, D); pools (P, KV, page_size, D); page_idx (B, max_pages)
    int32; pos scalar or (B,) int32.  Returns (B, H, T, D).

    ``max_pages * page_size`` is the logical max_len.  Unmapped page-table
    entries must be 0 (the null page); ``active`` defaults to ``pos >= 0``.
    T > 1 is the speculative multi-token verify block: query row ``t``
    attends logical keys ``kpos <= pos[b] + t`` — the page indirection
    never changes the mask math.  ``k_scale``/``v_scale``
    (P, KV, page_size, 1) f32 select the quantized path: K/V blocks are
    dequantized in VMEM right after the page DMA.
    """
    out = _paged_decode_call(q, k_pages, v_pages, page_idx, pos, active,
                             window=window, k_scale=k_scale,
                             v_scale=v_scale, num_splits=1,
                             interpret=interpret)
    return out.reshape(q.shape)


# --------------------------------------------------------------- prefill
def _paged_prefill_kernel(page_ref, off_ref, q_ref, k_ref, v_ref, *rest,
                          window: int, page_size: int, scale: float, tq: int,
                          quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    ip = pl.program_id(1)
    n_pages = pl.num_programs(1)
    pos = off_ref[0]  # absolute position of query row 0

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ip * page_size

    @pl.when(_block_needed(pos, 1, k_start, page_size, window, tq))
    def _compute():
        _accumulate_page(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                         acc_ref, k_start=k_start, pos=pos, window=window,
                         scale=scale, tq=tq, page_size=page_size, quant=quant)

    @pl.when(ip == n_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_prefill_attention_tpu(q, k_pages, v_pages, page_row, q_offset, *,
                                window=0, k_scale=None, v_scale=None,
                                interpret=False):
    """Fused paged prefill: q (1, H, C, D) — one slot's chunk of C query
    rows at absolute offset ``q_offset`` — vs pools (P, KV, page_size, D)
    through that slot's page-table row ``page_row (max_pages,)`` int32.
    Returns (1, H, C, D).

    The chunk's own K/V must already be written to the pages (the update
    runs first), so row ``t`` attends logical keys
    ``kpos <= q_offset + t`` — causal against the prefix *and* within the
    chunk, exactly ``flash_attention_xla(..., q_offset=offset)`` over the
    gathered view, with the gather folded into the page DMA.
    """
    b, h, tq, d = q.shape
    assert b == 1, ("fused paged prefill is one slot per call", q.shape)
    _, kv, page_size, _ = k_pages.shape
    max_pages = page_row.shape[0]
    quant = k_scale is not None
    g = h // kv
    scale = d ** -0.5
    page_row = jnp.asarray(page_row, jnp.int32).reshape(-1)
    off = jnp.asarray(q_offset, jnp.int32).reshape(1)

    kernel = functools.partial(_paged_prefill_kernel, window=window,
                               page_size=page_size, scale=scale, tq=tq,
                               quant=quant)
    kv_map = lambda h_, ip, pr_, off_: (pr_[ip], h_ // g, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, tq, d), lambda h_, ip, pr_, off_: (0, h_, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d), kv_map),
        pl.BlockSpec((1, 1, page_size, d), kv_map),
    ]
    operands = [q, k_pages, v_pages]
    if quant:
        in_specs += [_page_scale_spec(page_size, kv_map)] * 2
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_row, q_offset
        grid=(h, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, tq, d),
                               lambda h_, ip, pr_, off_: (0, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, 1), jnp.float32),
            pltpu.VMEM((tq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, h, tq, d), q.dtype),
        interpret=interpret,
    )(page_row, off, *operands)


# --------------------------------------------------------------- split-K
def paged_decode_attention_splitk_tpu(q, k_pages, v_pages, page_idx, pos, *,
                                      active=None, window=0, num_splits=4,
                                      k_scale=None, v_scale=None,
                                      interpret=False):
    """Two-phase (split-K) paged flash-decode; same contract as
    ``paged_decode_attention_tpu`` but phase 1 partitions the *page table*
    into ``num_splits`` disjoint page ranges (``max_pages % num_splits``
    must be 0 — splits align to page boundaries, never raw key counts) and
    phase 2 reuses the dense combine kernel.  Single-token only.
    """
    b, h, tq, d = q.shape
    assert tq == 1, ("split-K paged decode is single-token; multi-token "
                     "verify uses paged_decode_attention_tpu", q.shape)
    ns = num_splits
    parts = _paged_decode_call(q, k_pages, v_pages, page_idx, pos, active,
                               window=window, k_scale=k_scale,
                               v_scale=v_scale, num_splits=ns,
                               interpret=interpret)
    # (B, ns, KV, g, n) -> (B, H, ns, n): the combine kernel's layout
    o_parts, ms, ls = (x.reshape(b, ns, h, x.shape[-1]).swapaxes(1, 2)
                       for x in parts)

    return pl.pallas_call(
        _splitk_combine_kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, ns, d), lambda b_, h_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, ns, 1), lambda b_, h_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, ns, 1), lambda b_, h_: (b_, h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda b_, h_: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(o_parts, ms, ls)
