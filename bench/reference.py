"""Plain float32 reference of the served decoder, independent of the program.

It imports nothing of ``repro`` and reads no array the program made: the
weights are regenerated here, layer by layer, from the run's seed
(``weights.py``).  Every matrix product runs at
``jax.lax.Precision.HIGHEST`` (float32 on the TPU, not one bf16 pass).

The block follows the repo's dense decoder, which departs from the
published models as the configuration files' ``assumed`` say: RMSNorm
(scale only), RoPE with the split-half rotation, no biases, and for a
non-gated MLP the tanh form of GELU.  The unembedding is a table of its
own, or the embedding table where the configuration ties them.  Attention is
causal GQA/MQA with scale ``head_dim ** -0.5`` and a float32 softmax.

The sequences are run one at a time, each padded at its end to a bucket of
``BUCKET`` positions (padding past a causal sequence changes none of its
rows), attention in blocks of ``Q_BLOCK`` query rows and the MLP in blocks
of ``ROW_BLOCK`` rows, so a 13-layer, 6,144-wide model fits beside nothing
else on one chip.

``lowp=True`` is the correctness check's control: the same forward with
every weight matrix and every cached key and value rounded to float8
(e4m3, one absmax scale per output column or per token and head), the
precision below the bf16 the configurations serve in.
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


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x (S, H, hd) at positions 0..S-1, split-half rotation."""
    s, _, hd = x.shape
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, hd, 2) / hd)),
                        jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def mlp(x, w, gated):
    def rows(xb):
        if gated:
            hid = (jax.nn.silu(jnp.dot(xb, w["w_gate"], precision=HI))
                   * jnp.dot(xb, w["w_up"], precision=HI))
        else:
            hid = jax.nn.gelu(jnp.dot(xb, w["w_up"], precision=HI),
                              approximate=True)
        return jnp.dot(hid, w["w_down"], precision=HI)

    s, d = x.shape
    return jax.lax.map(rows, x.reshape(s // ROW_BLOCK, ROW_BLOCK, d)
                       ).reshape(s, d)


def _lowp_weights(w):
    """Every matrix rounded to fp8 with one scale per output column."""
    a = w["attn"]
    return dict(w, attn={"wq": _fp8(a["wq"], 0), "wk": _fp8(a["wk"], 0),
                         "wv": _fp8(a["wv"], 0), "wo": _fp8(a["wo"], (0, 1))},
                mlp={k: _fp8(m, 0) for k, m in w["mlp"].items()})


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: W.Dims, lowp: bool):
    def layer(h, w):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        if lowp:
            w = _lowp_weights(w)
        a = w["attn"]
        x = rmsnorm(h, w["ln1"]["scale"], dims.eps)
        q = rope(jnp.einsum("sd,dhk->shk", x, a["wq"], precision=HI),
                 dims.rope_theta)
        k = rope(jnp.einsum("sd,dhk->shk", x, a["wk"], precision=HI),
                 dims.rope_theta)
        v = jnp.einsum("sd,dhk->shk", x, a["wv"], precision=HI)
        if lowp:
            k, v = _fp8(k, -1), _fp8(v, -1)
        h = h + jnp.einsum("shk,hkd->sd", attention(q, k, v), a["wo"],
                           precision=HI)
        return h + mlp(rmsnorm(h, w["ln2"]["scale"], dims.eps), w["mlp"],
                       dims.gated)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(dims: W.Dims, lowp: bool):
    def logits(h, table, scale):
        table = table.astype(jnp.float32)
        if lowp:
            table = _fp8(table, 1)
        x = rmsnorm(h, scale.astype(jnp.float32), dims.eps)
        return jnp.dot(x, table.T, precision=HI)

    return jax.jit(logits)


def logits(dims: W.Dims, seed: int, seqs, rows, *, lowp=False):
    """Logits at ``rows[i]`` of sequence ``seqs[i]`` (token ids), one
    (len(rows[i]), vocab) float32 array per sequence, as numpy."""
    table, final, unembed = W.head(dims, seed)
    hs = []
    for s in seqs:
        pad = -(-len(s) // BUCKET) * BUCKET
        tok = np.zeros(pad, np.int32)
        tok[:len(s)] = s
        hs.append(jnp.take(table, jnp.asarray(tok), axis=0)
                  .astype(jnp.float32))
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
        out.append(np.asarray(head(h[jnp.asarray(idx)], unembed,
                                   final))[:len(r)])
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
