"""LM: the composable model wrapper used by training, serving, and dry-run.

``LM`` is a plain object holding the arch config + runtime knobs; all methods
are pure functions of explicit params/caches and safe to ``jax.jit``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .layers import (chunked_ce_loss, embed, embed_positions, embedding_init,
                     norm, norm_init, position_embedding_init, unembed)
from .transformer import (apply_blocks, apply_blocks_decode,
                          apply_blocks_prefill_chunk, cache_batch_axes,
                          copy_cache_in, copy_cache_out, copy_cache_pages,
                          copy_cache_pages_across, init_blocks, init_cache,
                          init_cache_paged, supports_chunked_prefill,
                          supports_paged_cache, supports_speculative,
                          unzip_prefill_buf, zip_prefill_buf)

MOE_LB_COEF = 0.01
MOE_Z_COEF = 1e-3


def _identity_shard(name: str, x):
    return x


@dataclasses.dataclass(frozen=True)
class RuntimeKnobs:
    """Perf / execution knobs — the hillclimbing surface."""

    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    cache_dtype: Any = jnp.bfloat16
    q_chunk: int = 512  # flash-attention query block
    ce_chunk: int = 1024  # chunked cross-entropy block
    remat: bool = True
    use_pallas: bool = False  # Pallas kernels (TPU); XLA path otherwise
    causal_skip: bool = False  # unrolled causal block-skip attention (H2)
    # 0 = auto (the serving engine picks per step from (max(pos), batch) via
    # runtime.steps.pick_decode_splits); >= 1 is a static override.  Both 0
    # and 1 lower to the single-pass kernel outside the engine.
    decode_splits: int = 0
    # "" = full-precision paged KV; "int8"/"fp8" store quantized page pools
    # with per-token/per-head scale leaves, dequantized inside the paged
    # kernels (~2x/4x pages per HBM byte).  Paged caches only.
    kv_quant: str = ""
    shard_fn: Callable = _identity_shard  # sharding-constraint hook

    def with_(self, **kw) -> "RuntimeKnobs":
        return dataclasses.replace(self, **kw)


class LM:
    def __init__(self, cfg, knobs: Optional[RuntimeKnobs] = None):
        self.cfg = cfg
        self.knobs = knobs or RuntimeKnobs()

    # ------------------------------------------------------------- params
    def init(self, key) -> dict:
        cfg, dt = self.cfg, self.knobs.param_dtype
        k1, k2 = jax.random.split(key)
        params = {
            "embed": embedding_init(k1, cfg.vocab_size, cfg.d_model,
                                    cfg.tie_embeddings, dt),
            "blocks": init_blocks(k2, cfg, dt),
            "final_norm": norm_init(cfg, dt),
        }
        if cfg.position_embeddings == "learned":
            params["pos_embed"] = position_embedding_init(
                jax.random.fold_in(key, 2), cfg.max_positions, cfg.d_model, dt)
        return params

    def param_specs(self):
        """Abstract params (no allocation) for the dry-run."""
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    # ------------------------------------------------------------ forward
    def _embed(self, params, tokens, start, embeds=None):
        """Hidden states of ``tokens`` (B, T) (or of ``embeds``, the
        embeddings input mode) at absolute positions ``start + t``;
        ``start`` is a scalar or a (B,) vector of per-slot positions.
        Where the config learns its positions, the table's rows at those
        positions are added (summed in float32).  Under the device
        program's ``embed`` scope."""
        dt = self.knobs.compute_dtype
        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens) if embeds is None else embeds
            if self.cfg.position_embeddings != "learned":
                return x.astype(dt)
            start = jnp.asarray(start, jnp.int32)
            positions = ((start.reshape(-1, 1) if start.ndim else start)
                         + jnp.arange(tokens.shape[1]))
            pos = embed_positions(params["pos_embed"], positions)
            return (x.astype(jnp.float32) + pos.astype(jnp.float32)).astype(dt)

    def hidden(self, params, batch, mode: str):
        embeds = (batch["embeds"].astype(self.knobs.compute_dtype)
                  if self.cfg.input_mode == "embeddings" else None)
        x = self._embed(params, batch["tokens"], 0, embeds)
        x = self.knobs.shard_fn("hidden", x)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x, aux, caches = apply_blocks(params["blocks"], x, positions,
                                      cfg=self.cfg, knobs=self.knobs, mode=mode)
        x = norm(params["final_norm"], x, self.cfg)
        return x, aux, caches

    # --------------------------------------------------------------- loss
    def loss(self, params, batch):
        """Next-token CE (+ MoE aux).  batch: tokens (B,S) [+ embeds]."""
        x, aux, _ = self.hidden(params, batch, mode="train")
        tokens = batch["tokens"]
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        mask = jnp.pad(jnp.ones_like(tokens[:, 1:], jnp.float32),
                       ((0, 0), (0, 1)))
        ce = chunked_ce_loss(params["embed"], x, targets, mask,
                             chunk=self.knobs.ce_chunk)
        loss = ce
        metrics = {"ce_loss": ce}
        if aux:
            n_moe = max(1, sum(1 for k in build_kinds(self.cfg) if k == "moe"))
            lb = aux["moe_lb_loss"] / n_moe
            zl = aux["moe_z_loss"] / n_moe
            loss = loss + MOE_LB_COEF * lb + MOE_Z_COEF * zl
            metrics.update(moe_lb_loss=lb, moe_z_loss=zl,
                           moe_drop_frac=aux["moe_drop_frac"] / n_moe)
        metrics["loss"] = loss
        return loss, metrics

    # ------------------------------------------------------------ prefill
    def prefill(self, params, batch):
        """Returns (last-position logits (B,V), caches)."""
        x, _, caches = self.hidden(params, batch, mode="prefill")
        logits = unembed(params["embed"], x[:, -1:, :])[:, 0, :]
        return logits.astype(jnp.float32), caches

    # ------------------------------------------------------------- decode
    def _unembed(self, params, x):
        """Final norm and unembedding of the cached (serving) paths,
        under the device program's ``unembed`` scope."""
        with jax.named_scope("unembed"):
            return unembed(params["embed"],
                           norm(params["final_norm"], x, self.cfg))

    def decode_step(self, params, caches, tokens, pos):
        """tokens (B,1) int32 -> (logits (B,V), new caches).

        ``pos`` is a scalar (all slots in lockstep) or a (B,) vector of
        per-slot positions (ragged continuous batching); slots parked at
        pos = -1 are inactive and produce don't-care logits.
        """
        x = self._embed(params, tokens, pos)
        x, new_caches = apply_blocks_decode(params["blocks"], x, caches, pos,
                                            cfg=self.cfg, knobs=self.knobs)
        logits = self._unembed(params, x)[:, 0, :]
        return logits.astype(jnp.float32), new_caches

    def prefill_chunk_step(self, params, caches, tokens, slot, offset):
        """Chunked prefill: one slot's prompt chunk.

        tokens (1,C) int32 at absolute positions offset..offset+C-1; writes
        the chunk's K/V into ``caches`` at (slot, offset) and returns
        (chunk logits (C,V) fp32, new caches).  The engine reads the logits
        row of the last real prompt token to seed decode.
        """
        x = self._embed(params, tokens, offset)
        x, new_caches = apply_blocks_prefill_chunk(
            params["blocks"], x, caches, slot, offset, cfg=self.cfg,
            knobs=self.knobs)
        logits = self._unembed(params, x)[0]
        return logits.astype(jnp.float32), new_caches

    def supports_chunked_prefill(self) -> bool:
        return supports_chunked_prefill(self.cfg)

    # -------------------------------------------- speculative (multi-token)
    def supports_speculative(self) -> bool:
        return supports_speculative(self.cfg)

    def decode_step_spec(self, params, caches, tokens, pos):
        """Multi-token verify step.  tokens (B,T) int32 — the current
        feed token plus up to T-1 drafted continuations at absolute
        positions ``pos[b] .. pos[b] + T-1`` — -> (logits (B,T,V) fp32,
        new caches).

        All T K/V pairs are written to the cache before attention runs,
        and the mask is causal within the draft block, so logits row
        ``t`` is the target model's next-token distribution *given* the
        draft prefix tokens[:, :t+1] — exactly what sequential decode
        would have produced at that position.  Rejected drafts roll back
        by position truncation: the engine simply resumes at the last
        accepted position and later writes overwrite the stale K/V,
        which the position mask keeps unattended until then.
        """
        x = self._embed(params, tokens, pos)
        x, new_caches = apply_blocks_decode(params["blocks"], x, caches, pos,
                                            cfg=self.cfg, knobs=self.knobs)
        logits = self._unembed(params, x)
        return logits.astype(jnp.float32), new_caches

    def decode_step_spec_paged(self, params, caches, tokens, pos, page_idx,
                               *, page_size: int):
        """Paged ``decode_step_spec``: draft K/V land in the physical
        pages the slot's page-table row maps (positions past the mapped
        span write the null page — see
        ``attention.paged_cache_update_multi``)."""
        x = self._embed(params, tokens, pos)
        x, new_caches = apply_blocks_decode(params["blocks"], x, caches, pos,
                                            cfg=self.cfg, knobs=self.knobs,
                                            paged=(page_idx, page_size))
        logits = self._unembed(params, x)
        return logits.astype(jnp.float32), new_caches

    # -------------------------------------------------------- paged cache
    def supports_paged_cache(self) -> bool:
        return supports_paged_cache(self.cfg)

    def decode_step_paged(self, params, caches, tokens, pos, page_idx, *,
                          page_size: int):
        """Paged ``decode_step``: caches are global page pools and slot
        ``b``'s KV prefix lives in pages ``page_idx[b]`` (0 = null page).
        ``page_size`` is static per engine."""
        x = self._embed(params, tokens, pos)
        x, new_caches = apply_blocks_decode(params["blocks"], x, caches, pos,
                                            cfg=self.cfg, knobs=self.knobs,
                                            paged=(page_idx, page_size))
        logits = self._unembed(params, x)[:, 0, :]
        return logits.astype(jnp.float32), new_caches

    def prefill_chunk_step_paged(self, params, caches, tokens, slot, offset,
                                 page_idx, *, page_size: int):
        """Paged ``prefill_chunk_step``: the chunk (C a multiple of
        ``page_size``, ``offset`` page-aligned) writes the physical pages
        the slot's page-table row maps."""
        x = self._embed(params, tokens, offset)
        x, new_caches = apply_blocks_prefill_chunk(
            params["blocks"], x, caches, slot, offset, cfg=self.cfg,
            knobs=self.knobs, paged=(page_idx, page_size))
        logits = self._unembed(params, x)[0]
        return logits.astype(jnp.float32), new_caches

    def prefill_chunk_step_paged_buf(self, params, caches, tokens, slot,
                                     offset, page_idx, buf, *,
                                     page_size: int, gather: bool = False):
        """Buffered paged ``prefill_chunk_step`` (XLA path): ``buf`` is a
        dense ``init_cache(1, max_len)`` tree carried across the chunk
        loop — each layer reuses its (1, S, KV, D) slot view instead of
        re-gathering the full page chain every chunk.  ``gather=True``
        (first chunk of a prefix-cache hit) rebuilds the view from the
        page table once.  Returns (logits, new caches, new buf)."""
        merged = zip_prefill_buf(caches, buf)
        x = self._embed(params, tokens, offset)
        x, new_merged = apply_blocks_prefill_chunk(
            params["blocks"], x, merged, slot, offset, cfg=self.cfg,
            knobs=self.knobs, paged=(page_idx, page_size), gather=gather)
        new_caches, new_buf = unzip_prefill_buf(new_merged)
        logits = self._unembed(params, x)[0]
        return logits.astype(jnp.float32), new_caches, new_buf

    def copy_cache_pages(self, caches, src, dst):
        """Device half of CoW: duplicate physical page src -> dst in every
        layer pool."""
        return copy_cache_pages(caches, src, dst)

    def copy_cache_pages_across(self, src_caches, dst_caches, src_idx, dst_idx):
        """Cross-engine page transfer: gather ``src_idx`` pages from one
        pool, scatter them at ``dst_idx`` in another (disagg handoff)."""
        return copy_cache_pages_across(src_caches, dst_caches, src_idx, dst_idx)

    # ------------------------------------------------- checkpoint/restore
    def cache_batch_axes(self, max_len: int):
        """Per-leaf batch-axis tree of the dense cache (host-side)."""
        return cache_batch_axes(self.cfg, self.knobs, max_len)

    def copy_cache_out(self, caches, slot, axes):
        """Slice slot ``slot``'s stripe from every dense cache leaf — the
        device half of a preemption checkpoint (KV and, for SSM/hybrid
        plans, recurrent state alike)."""
        return copy_cache_out(caches, slot, axes)

    def copy_cache_in(self, caches, snapshot, slot, axes):
        """Restore a ``copy_cache_out`` snapshot into slot ``slot``."""
        return copy_cache_in(caches, snapshot, slot, axes)

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int):
        return init_cache(self.cfg, self.knobs, batch, max_len)

    def init_cache_paged(self, num_pages: int, page_size: int):
        return init_cache_paged(self.cfg, self.knobs, num_pages, page_size)

    def cache_specs(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))


def build_kinds(cfg):
    return cfg.layer_kinds()


def build_model(arch: str, smoke: bool = False,
                knobs: Optional[RuntimeKnobs] = None) -> LM:
    from repro.configs import get_config

    return LM(get_config(arch, smoke=smoke), knobs)
