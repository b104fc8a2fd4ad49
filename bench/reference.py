"""Plain float32 reference of the served model, independent of the program.

It imports nothing of ``repro`` and reads no array the program made: the
weights are regenerated here, layer by layer, from the run's seed
(``weights.py``).  Every matrix product runs at
``jax.lax.Precision.HIGHEST`` (float32 on the TPU, not one bf16 pass).

The model's equations are its block's (``blocks/<block>.py``, whose
``Dims`` every function here takes): ``dims.embed(outer, tokens,
positions)`` makes the hidden states, ``dims.layer(h, w, lowp)`` runs one
layer and ``dims.head(outer, h)`` gives the logits, each on float32
weights.  What is here serves every block: the bucketing, causal
attention in blocks of query rows (``attention``), a row-blocked map for
the MLP (``row_blocks``) and the float8 rounding of the control
(``_fp8``, ``cached``).

The sequences are run one at a time, each padded at its end to a bucket of
``BUCKET`` positions (padding past a causal sequence changes none of its
rows; the padding's positions run on past the sequence, so a block with a
table of positions clips them), attention in blocks of ``Q_BLOCK`` query
rows and the MLP in blocks of ``ROW_BLOCK`` rows, so a 13-layer,
6,144-wide model fits beside nothing else on one chip.

``lowp=True`` is the correctness check's control: the same forward in
float8 e4m3, the precision below the bf16 the configurations serve in.
Every weight that ``dims.fp8_axes()`` names is rounded here, with one
absmax scale along the axes it gives (those a matrix reduces over, so one
scale per output column), and the block passes each key and value it
caches through ``cached`` (one scale per token and head).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
BUCKET = 1024
Q_BLOCK = 256
ROW_BLOCK = 1024
HEAD_ROWS = 256  # logits rows are computed in multiples of this
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def cached(x, lowp: bool):
    """Keys or values (S, KV, hd) as the control's cache holds them."""
    return _fp8(x, -1) if lowp else x


def _fp8_leaves(tree, axes: dict):
    """``tree`` with each leaf whose path ``axes`` names rounded to fp8
    along the axes it gives."""
    def leaf(path, a):
        name = "/".join(k.key for k in path)
        return _fp8(a, axes[name]) if name in axes else a

    return jax.tree_util.tree_map_with_path(leaf, tree)


def attention(q, k, v):
    """Causal attention: q (S, H, hd), k/v (S, KV, hd) -> (S, H, hd)."""
    s, h, hd = q.shape
    kv = k.shape[1]
    qg = q.reshape(s // Q_BLOCK, Q_BLOCK, kv, h // kv, hd)
    kpos = jnp.arange(s)

    def block(args):
        qb, i = args
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)

    out = jax.lax.map(block, (qg, jnp.arange(s // Q_BLOCK)))
    return out.reshape(s, h, hd)


def row_blocks(fn, x):
    """``fn`` over ``x`` (S, d) in blocks of ``ROW_BLOCK`` rows."""
    s, d = x.shape
    out = jax.lax.map(fn, x.reshape(s // ROW_BLOCK, ROW_BLOCK, d))
    return out.reshape(s, out.shape[-1])


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _weights(tree, dims, lowp: bool):
    tree = _f32(tree)
    return _fp8_leaves(tree, dims.fp8_axes()) if lowp else tree


@functools.lru_cache(maxsize=None)
def _layer_fn(dims, lowp: bool):
    return jax.jit(lambda h, w: dims.layer(h, _weights(w, dims, lowp), lowp))


@functools.lru_cache(maxsize=None)
def _head_fn(dims, lowp: bool):
    return jax.jit(lambda h, outer: dims.head(_weights(outer, dims, lowp), h))


def logits(dims, seed: int, seqs, rows, *, lowp=False):
    """Logits at ``rows[i]`` of sequence ``seqs[i]`` (token ids), one
    (len(rows[i]), vocab) float32 array per sequence, as numpy."""
    outer = W.head(dims, seed)
    hs = []
    for s in seqs:
        pad = -(-len(s) // BUCKET) * BUCKET
        tok = np.zeros(pad, np.int32)
        tok[:len(s)] = s
        hs.append(dims.embed(outer, jnp.asarray(tok),
                             jnp.arange(pad, dtype=jnp.int32)))
    layer = _layer_fn(dims, lowp)
    for i in range(dims.layers):
        w = W.layer(dims, seed, i)
        hs = [layer(h, w) for h in hs]
        del w
    head = _head_fn(dims, lowp)
    out = []
    for h, r in zip(hs, rows):
        # rows padded to a whole block, so one compiled head serves all
        idx = np.full(-(-len(r) // HEAD_ROWS) * HEAD_ROWS, r[-1], np.int32)
        idx[:len(r)] = r
        out.append(np.asarray(head(h[jnp.asarray(idx)], outer))[:len(r)])
    return out


def served_rows(prompts, outputs):
    """The sequences the reference runs (each prompt and its served tokens
    but the last) and the rows whose logits chose each served token."""
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(o[:-1], np.int32)])
            for p, o in zip(prompts, outputs)]
    rows = [len(p) - 1 + np.arange(len(o)) for p, o in zip(prompts, outputs)]
    return seqs, rows


def gaps(ref_logits, tokens):
    """Per position, how far the reference's logit of ``tokens`` lies below
    its best logit (0 where the token is the reference's argmax)."""
    return [lg.max(axis=1) - lg[np.arange(len(t)), np.asarray(t)]
            for lg, t in zip(ref_logits, tokens)]
