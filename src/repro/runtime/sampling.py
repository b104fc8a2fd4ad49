"""Per-request sampling: temperature / top-k / top-p with per-slot PRNG keys.

The serving engine decodes a fixed slot batch with static shapes, so the
sampling parameters ride along as *per-slot arrays* — ``temp[B]``,
``top_k[B]``, ``top_p[B]``, ``keys[B, 2]`` — and one compiled step serves
every mix of greedy and sampled requests.  Determinism is per request: a
request's key is derived from its seed once at admission and ``fold_in``'d
with the decode position each step, so replaying the same request (same
seed, same prompt) reproduces its tokens regardless of which slot it lands
in or what its neighbors are doing.

``temperature <= 0`` is the greedy contract: the returned token is the
plain fp32 ``argmax`` of the raw logits — bitwise identical to the
pre-sampling greedy path (``tests/test_serving_api.py`` holds the engine
to this across dense/paged caches).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling controls (the request-API half of ServeConfig).

    * ``temperature`` — 0 (default) decodes greedily; > 0 samples from the
      scaled distribution.
    * ``top_k`` — keep only the k highest-probability tokens (0 = off).
    * ``top_p`` — nucleus sampling: keep the smallest set of tokens whose
      cumulative probability reaches ``top_p`` (1.0 = off).
    * ``seed`` — per-request PRNG seed; ``None`` derives one from the
      request id so replays are deterministic by default.
    * ``stop`` — stop sequences: token ids (single-token stops, the
      ``eos_id`` generalization) or sequences of token ids (multi-token
      stops).  Generation finishes the step the output *ends with* any of
      them; matched tokens stay in the output.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: Optional[int] = None
    stop: Tuple = ()

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0: {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0: {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1]: {self.top_p}")

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0

    @functools.cached_property
    def stop_sequences(self) -> Tuple[Tuple[int, ...], ...]:
        """``stop`` normalized to tuples of ints (bare ids become 1-grams).
        Cached — ``matches_stop`` consults this after every token (the
        cache writes straight into ``__dict__``, bypassing frozen)."""
        out = []
        for s in self.stop:
            if isinstance(s, (int, np.integer)):
                out.append((int(s),))
            else:
                seq = tuple(int(t) for t in s)
                if seq:
                    out.append(seq)
        return tuple(out)

    def key_data(self, req_id: int) -> np.ndarray:
        """Raw (2,) uint32 PRNG key for this request (seed or req_id)."""
        seed = self.seed if self.seed is not None else req_id
        return np.asarray(jax.random.PRNGKey(seed % (2 ** 31)), np.uint32)


def matches_stop(output: Sequence[int], params: SamplingParams,
                 eos_id: int = -1) -> Optional[str]:
    """Host-side stop check: the finish reason the tail of ``output``
    triggers ("eos" / "stop"), or None."""
    n = len(output)
    if not n:
        return None
    if eos_id >= 0 and output[-1] == eos_id:
        return "eos"
    for seq in params.stop_sequences:
        k = len(seq)
        if k <= n and tuple(output[n - k:]) == seq:
            return "stop"
    return None


def _topk_topp_mask(scaled, top_k, top_p):
    """Additive mask (0 keep / -inf drop) for per-row top-k + top-p.

    Both filters are applied in the sorted domain off one argsort, then
    scattered back through the inverse permutation; the best token is
    always kept so the row never masks to nothing.
    """
    v = scaled.shape[-1]
    sort_idx = jnp.argsort(-scaled, axis=-1)
    srt = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    ranks = jnp.broadcast_to(jnp.arange(v)[None, :], srt.shape)
    keep = jnp.where(top_k[:, None] > 0, ranks < top_k[:, None], True)
    # exclusive cumulative mass below top_p keeps the crossing token too
    keep = keep & ((cum - probs) < top_p[:, None])
    keep = keep.at[:, 0].set(True)
    mask_sorted = jnp.where(keep, 0.0, -jnp.inf).astype(scaled.dtype)
    inv = jnp.argsort(sort_idx, axis=-1)
    return jnp.take_along_axis(mask_sorted, inv, axis=-1)


def greedy_tokens(logits):
    """Argmax over the last (vocabulary) axis as int32: the greedy pick
    of every serving step, under the device program's ``sample`` scope."""
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_tokens(logits, pos, temp, top_k, top_p, keys):
    """Sample (or greedily pick) one token per row, static shapes.

    logits (B, V) fp32; pos (B,) int32 (folded into each row's key so every
    step draws fresh randomness deterministically); temp (B,) fp32;
    top_k (B,) int32 (0 = off); top_p (B,) fp32 (1 = off); keys (B, 2)
    uint32 raw PRNG key data.  Rows with ``temp <= 0`` return the raw-logit
    argmax — bitwise the greedy path, untouched by the sampling math.
    """
    with jax.named_scope("sample"):
        greedy = greedy_tokens(logits)
        safe_t = jnp.maximum(temp, 1e-6)[:, None]
        scaled = (logits / safe_t).astype(jnp.float32)
        masked = scaled + _topk_topp_mask(scaled, top_k, top_p)

        def draw(key, p, row):
            return jax.random.categorical(
                jax.random.fold_in(key, jnp.maximum(p, 0)), row)

        sampled = jax.vmap(draw)(keys, pos, masked).astype(jnp.int32)
        return jnp.where(temp > 0, sampled, greedy)


def sample_tokens_multi(logits, pos, temp, top_k, top_p, keys):
    """Per-row target draws for a speculative verify block, static shapes.

    logits (B, T, V) fp32 — row ``t`` of slot ``b`` is the target
    model's distribution at absolute position ``pos[b] + t`` (given the
    draft prefix); pos (B,) int32; temp/top_k/top_p (B,) and keys (B, 2)
    are the *per-slot* arrays, shared by every row of a slot.  Returns
    (B, T) int32.

    Each row folds its own absolute position into the slot's key —
    exactly the fold the non-speculative step would have used when it
    reached that position — so an accepted draw is **bitwise the token
    the baseline engine would have sampled there** (and rows with
    ``temp <= 0`` are the bitwise-greedy argmax).  That makes the
    accept-on-equality rule of ``speculative_accept`` an exact rejection
    sampler: every emitted token is a faithful draw from the target
    distribution conditioned on the (verified) prefix.
    """
    b, t, v = logits.shape
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    pos_rows = (pos[:, None] + jnp.arange(t)[None, :]).reshape(-1)
    rep = lambda x: jnp.repeat(x, t, axis=0)
    out = sample_tokens(logits.reshape(b * t, v), pos_rows, rep(temp),
                        rep(top_k), rep(top_p), rep(keys))
    return out.reshape(b, t)


def speculative_accept(draft, target) -> int:
    """Host-side acceptance rule: the number of draft tokens confirmed by
    the verify pass.

    ``draft`` is the k <= T-1 proposed tokens; ``target`` is the (T,)
    verify-step output where ``target[t]`` is the token the target model
    emits *after* feed + draft[:t].  Draft token ``t`` survives iff every
    earlier draft survived and ``draft[t] == target[t]`` — the emitted
    tokens are then ``target[:m + 1]`` (the m accepted drafts, which
    equal the target's own choices, plus the free correction/bonus
    token), so the output stream is exactly what non-speculative decode
    would have produced token by token.  Greedy verify makes this
    deterministic lockstep; sampled verify compares against the
    position-keyed target draw, which preserves the target distribution
    exactly (see ``sample_tokens_multi``).
    """
    m = 0
    for d, t in zip(draft, target):
        if int(d) != int(t):
            break
        m += 1
    return m
