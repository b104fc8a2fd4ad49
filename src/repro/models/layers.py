"""Shared layers: RMSNorm and LayerNorm, RoPE, MLP, embeddings, chunked CE
loss.

All functions are pure; parameters are plain dicts of jnp arrays.  Weight
init returns fp32 or the requested param dtype; compute happens in the dtype
of the activations (bf16 in production, fp32 in small CPU tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _init(key, shape, scale=0.02, dtype=jnp.float32):
    return (scale * jax.random.normal(key, shape, dtype=jnp.float32)).astype(dtype)


# ---------------------------------------------------------------- rmsnorm
def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(dt)


# -------------------------------------------------------------- layernorm
def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype=dtype),
            "bias": jnp.zeros((d,), dtype=dtype)}


def layernorm(params, x, eps: float):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    out = xc * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)
    return (out + params["bias"].astype(jnp.float32)).astype(dt)


def norm_init(cfg, dtype=jnp.float32):
    """The params of one of ``cfg``'s norms (``cfg.norm``)."""
    init = layernorm_init if cfg.norm == "layernorm" else rmsnorm_init
    return init(cfg.d_model, dtype)


def norm(params, x, cfg):
    """``cfg``'s norm (``cfg.norm`` at ``cfg.norm_eps``) of ``x``."""
    if cfg.norm == "layernorm":
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


# ------------------------------------------------------------------- rope
def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta), dtype=jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------------- mlp
def mlp_init(key, d_model: int, d_ff: int, gated: bool, dtype=jnp.float32,
             bias: bool = False):
    ks = jax.random.split(key, 3)
    p = {"w_down": _init(ks[2], (d_ff, d_model), dtype=dtype)}
    if gated:
        p["w_gate"] = _init(ks[0], (d_model, d_ff), dtype=dtype)
        p["w_up"] = _init(ks[1], (d_model, d_ff), dtype=dtype)
    else:
        p["w_up"] = _init(ks[1], (d_model, d_ff), dtype=dtype)
    if bias:
        p["b_up"] = jnp.zeros((d_ff,), dtype=dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype=dtype)
    return p


def mlp(params, x, gated: bool, shard_fn=lambda name, v: v):
    """``shard_fn("mlp_up", ...)`` is the TP seam of the gather-form
    serving layout (sharding/rules.py ``ServeShardFn``): it all-gathers
    the ff-sharded up/gate projections so the activation and the down
    projection run replicated, in the single-device order — the
    constraint that keeps sharded decode bitwise-identical.  The seam
    sits on the dot outputs, BEFORE the activation: gathering after it
    lets the partitioner compute the activation on the local shard,
    whose fused lowering differs from the full-width one by ~1 ulp
    (measured on CPU; see tests/test_sharded_serve.py).  ``b_up`` and
    ``b_down``, where ``params`` has them, are added after each product."""
    if gated:
        g = shard_fn("mlp_up", x @ params["w_gate"])
        u = shard_fn("mlp_up", _biased(x @ params["w_up"], params, "b_up"))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(shard_fn("mlp_up",
                                 _biased(x @ params["w_up"], params, "b_up")))
    return _biased(h @ params["w_down"], params, "b_down")


def _biased(y, params, name: str):
    return y + params[name] if name in params else y


# ------------------------------------------------------------- embeddings
def embedding_init(key, vocab: int, d_model: int, tied: bool, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    p = {"table": _init(ks[0], (vocab, d_model), dtype=dtype)}
    if not tied:
        p["head"] = _init(ks[1], (vocab, d_model), dtype=dtype)
    return p


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def position_embedding_init(key, max_positions: int, d_model: int,
                            dtype=jnp.float32):
    return {"table": _init(key, (max_positions, d_model), dtype=dtype)}


def embed_positions(params, positions):
    """Rows of the learned position table at ``positions``; a position past
    the table (a draft's padding, an idle slot's -1) reads its nearest
    row."""
    return jnp.take(params["table"], positions, axis=0, mode="clip")


def unembed(params, x):
    head = params.get("head", params["table"])
    return x @ head.T


# --------------------------------------------------- chunked CE next-token
def chunked_ce_loss(emb_params, x, targets, mask, chunk: int = 1024):
    """Next-token cross-entropy without materializing (B, S, V) logits.

    x: (B, S, d) final hidden states;  targets/mask: (B, S).
    Scans over sequence chunks; inside each chunk the (B, chunk, V) logits
    exist only transiently (and vocab-sharded under pjit).
    """
    b, s, d = x.shape
    chunk = min(chunk, s)
    n = s // chunk
    assert s % chunk == 0, f"seq {s} not divisible by CE chunk {chunk}"
    head = emb_params.get("head", emb_params["table"])

    xs = x.reshape(b, n, chunk, d).swapaxes(0, 1)  # (n, B, chunk, d)
    ts = targets.reshape(b, n, chunk).swapaxes(0, 1)
    ms = mask.reshape(b, n, chunk).swapaxes(0, 1)

    def body(carry, inp):
        tot, cnt = carry
        xc, tc, mc = inp
        logits = (xc @ head.T).astype(jnp.float32)  # (B, chunk, V)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return (tot + nll.sum(), cnt + mc.sum()), None

    # nested remat: the scan VJP would otherwise store (B, chunk, V) fp32
    # logits for every chunk — i.e. the full logits tensor
    body = jax.checkpoint(body)
    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (xs, ts, ms))
    return tot / jnp.maximum(cnt, 1.0)
