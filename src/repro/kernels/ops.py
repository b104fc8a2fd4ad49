"""Jit'd public wrappers around the Pallas kernels.

The model layer calls these with its own (B, S, H, D) layout; wrappers
transpose to the kernels' (B, H, S, D) layout.  ``interpret=None`` picks
from the backend: compiled Mosaic kernels on TPU, the Pallas interpreter
anywhere else (the CPU tests).  There is no jnp fallback: a shape the TPU
compiler refuses fails at compile time (tests/test_tpu_compile.py compiles
the serving kernels for a described v5e chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .decode_attention import decode_attention_splitk_tpu, decode_attention_tpu
from .flash_attention import flash_attention_tpu
from .paged_attention import (paged_decode_attention_splitk_tpu,
                              paged_decode_attention_tpu,
                              paged_prefill_attention_tpu)
from .ssd_scan import ssd_chunk_tpu


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@jax.named_scope("kv_layout")
def _kernel_layout(k_pages, v_pages, k_scale, v_scale):
    """Page pools (and scales, if any) from the model's (P, page_size, KV,
    D) into the paged kernels' (P, KV, page_size, D)."""
    def swap(x):
        return None if x is None else x.swapaxes(1, 2)

    return swap(k_pages), swap(v_pages), swap(k_scale), swap(v_scale)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=512,
                    block_k=512, interpret=None):
    """Model layout: q (B,S,H,D); k,v (B,S,KV,D) -> (B,S,H,D)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = flash_attention_tpu(qt, kt, vt, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "num_splits", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, active=None, window=0,
                     block_k=512, num_splits=1, interpret=None):
    """Model layout: q (B,T,H,D); caches (B,S,KV,D) -> (B,T,H,D).

    ``pos`` may be a scalar (lockstep) or a (B,) vector (ragged continuous
    batching); ``active`` (B,) 0/1 gates per-slot work (default pos >= 0).
    ``num_splits > 1`` selects the two-phase split-K path for long contexts.
    T > 1 is the speculative multi-token verify block (query row ``t``
    attends keys <= pos + t); it always takes the single-pass kernel —
    the split-K variant is single-token only.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    qt = q.swapaxes(1, 2)
    kt = k_cache.swapaxes(1, 2)
    vt = v_cache.swapaxes(1, 2)
    if num_splits > 1 and q.shape[1] == 1:
        out = decode_attention_splitk_tpu(qt, kt, vt, pos, active=active,
                                          window=window, block_k=block_k,
                                          num_splits=num_splits,
                                          interpret=interpret)
    else:
        out = decode_attention_tpu(qt, kt, vt, pos, active=active,
                                   window=window, block_k=block_k,
                                   interpret=interpret)
    return out.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("window", "num_splits",
                                             "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_idx, pos, *, active=None,
                           window=0, k_scale=None, v_scale=None, num_splits=1,
                           interpret=None):
    """Model layout: q (B,T,H,D); pools (P, page_size, KV, D); page_idx
    (B, max_pages) int32 -> (B,T,H,D).

    Paged mirror of ``decode_attention``: one program per slot copies
    the pages it attends through the scalar-prefetched page table.
    Unmapped entries must be 0 (null page); ``pos``/``active`` follow the
    ragged contract.  ``k_scale``/``v_scale`` (P, page_size, KV, 1) f32
    select the quantized (int8/fp8 pool) path; ``num_splits > 1`` selects
    the two-phase split-K path (single-token only, splits must divide
    max_pages — see ``pick_decode_splits``).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    qt = q.swapaxes(1, 2)
    kt, vt, kst, vst = _kernel_layout(k_pages, v_pages, k_scale, v_scale)
    if num_splits > 1 and q.shape[1] == 1:
        out = paged_decode_attention_splitk_tpu(
            qt, kt, vt, page_idx, pos, active=active, window=window,
            num_splits=num_splits, k_scale=kst, v_scale=vst,
            interpret=interpret)
    else:
        out = paged_decode_attention_tpu(qt, kt, vt, page_idx, pos,
                                         active=active, window=window,
                                         k_scale=kst, v_scale=vst,
                                         interpret=interpret)
    return out.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, page_idx, slot, offset, *,
                            window=0, k_scale=None, v_scale=None,
                            interpret=None):
    """Model layout: q (1,C,H,D) — one slot's prefill chunk at absolute
    ``offset`` — vs pools (P, page_size, KV, D) through row ``slot`` of
    ``page_idx (slots, max_pages)``.  Returns (1,C,H,D).

    Fused paged prefill: the chunk's K/V must already be written to the
    pages; no dense per-slot gather is materialized.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    qt = q.swapaxes(1, 2)
    kt, vt, kst, vst = _kernel_layout(k_pages, v_pages, k_scale, v_scale)
    page_row = jnp.take(jnp.asarray(page_idx, jnp.int32), slot, axis=0)
    out = paged_prefill_attention_tpu(qt, kt, vt, page_row, offset,
                                      window=window, k_scale=kst,
                                      v_scale=vst, interpret=interpret)
    return out.swapaxes(1, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x, b, c, dt, cum, *, interpret=None):
    """SSD intra-chunk compute; shapes per ssd_chunk_tpu docstring."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    return ssd_chunk_tpu(x, b, c, dt, cum, interpret=interpret)


# jnp oracles re-exported for convenience
attention_ref = ref.attention_ref
decode_attention_ref = ref.decode_attention_ref
paged_decode_attention_ref = ref.paged_decode_attention_ref
paged_decode_attention_quant_ref = ref.paged_decode_attention_quant_ref
paged_prefill_attention_ref = ref.paged_prefill_attention_ref
ssd_chunk_ref = ref.ssd_chunk_ref
