"""Seeded random weights in the served layout, drawn on the device.

The benchmark makes the weights itself, from ``--seed``, and hands them to
the program; the reference regenerates the same values one layer at a time
from the same seed, so it never reads an array the program holds.  Every
leaf of layer ``l`` comes from ``fold_in(fold_in(key, LAYERS), l)`` and its
own leaf index, so layer ``l`` of ``params(...)`` and ``layer(..., l)``
are the same numbers bit for bit.

Matrices are N(0, init_std) (0.02 unless the configuration says) and norm
scales 1 + N(0, 0.1), rounded to the served dtype.  The layout is the dense
decoder of ``configs/<name>.json``: an embedding table, an unembedding
table of its own (``head``) unless the configuration ties the two, per
layer RMSNorm, q/k/v/o projections with explicit head axes, and a SwiGLU
(``gated_mlp``) or two-matrix MLP.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

SCALE = 0.02
NORM_SCALE = 0.1
EMBED, LAYERS, FINAL_NORM, UNEMBED = 0, 1, 2, 3


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    rope_theta: float
    eps: float
    tied: bool = False  # the unembedding is the embedding table
    dtype: str = "bfloat16"  # the weights' served dtype
    init_std: float = SCALE   # standard deviation of every matrix

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        return cls(layers=model["num_hidden_layers"],
                   d_model=model["hidden_size"],
                   heads=model["num_attention_heads"],
                   kv_heads=model["num_key_value_heads"],
                   head_dim=model["head_dim"],
                   d_ff=model["intermediate_size"],
                   vocab=model["vocab_size"],
                   gated=model["gated_mlp"],
                   rope_theta=float(model["rope_theta"]),
                   eps=float(model["rms_norm_eps"]),
                   tied=bool(model["tie_word_embeddings"]),
                   dtype=model["dtype"],
                   init_std=float(model.get("init_std", SCALE)))


def seed_key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (``jax.random.key``
    keeps only the low 32)."""
    seed = int(seed) % 2**64
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def layer_shapes(dims: Dims) -> dict:
    """Leaf shapes of one decoder layer, in the program's param layout."""
    d, hd = dims.d_model, dims.head_dim
    mlp = {"w_up": (d, dims.d_ff), "w_down": (dims.d_ff, d)}
    if dims.gated:
        mlp["w_gate"] = (d, dims.d_ff)
    return {"ln1": {"scale": (d,)},
            "attn": {"wq": (d, dims.heads, hd), "wk": (d, dims.kv_heads, hd),
                     "wv": (d, dims.kv_heads, hd),
                     "wo": (dims.heads, hd, d)},
            "ln2": {"scale": (d,)},
            "mlp": mlp}


def _draw(key, path: str, shape, dtype, std):
    z = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("scale"):
        return (1.0 + NORM_SCALE * z).astype(dtype)
    return (std * z).astype(dtype)


def _tree(shapes: dict, key, dtype, std):
    """Draw every leaf of ``shapes``; leaf ``i`` in sorted-path order uses
    ``fold_in(key, i)``."""
    paths = sorted(_flatten(shapes))
    out: dict = {}
    for i, path in enumerate(paths):
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = _draw(jax.random.fold_in(key, i), path,
                                _get(shapes, parts), dtype, std)
    return out


def _flatten(tree, prefix=""):
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            yield from _flatten(v, path + "/")
        else:
            yield path


def _get(tree, parts):
    for p in parts:
        tree = tree[p]
    return tree


def _layer_key(key, index):
    return jax.random.fold_in(jax.random.fold_in(key, LAYERS), index)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims: Dims, dtype):
    return jax.jit(lambda key, index: _tree(
        layer_shapes(dims), _layer_key(key, index), dtype, dims.init_std))


def layer(dims: Dims, seed: int, index: int, dtype=None) -> dict:
    """Layer ``index``'s weights, the same numbers as ``params``'s
    ``blocks/stack[index]`` slice."""
    return _layer_fn(dims, dtype or dims.dtype)(seed_key(seed),
                                                jnp.int32(index))


def _embedding(key, dims, dtype):
    return _draw(jax.random.fold_in(key, EMBED), "table",
                 (dims.vocab, dims.d_model), dtype, dims.init_std)


def _embed(key, dims, dtype):
    """The ``embed`` group: ``table``, and ``head`` when untied."""
    out = {"table": _embedding(key, dims, dtype)}
    if not dims.tied:
        out["head"] = _draw(jax.random.fold_in(key, UNEMBED), "head",
                            (dims.vocab, dims.d_model), dtype, dims.init_std)
    return out


def _final_norm(key, dims, dtype):
    return _draw(jax.random.fold_in(key, FINAL_NORM), "scale",
                 (dims.d_model,), dtype, dims.init_std)


@functools.lru_cache(maxsize=None)
def _head_fn(dims: Dims, dtype):
    return jax.jit(lambda key: (_embed(key, dims, dtype),
                                _final_norm(key, dims, dtype)))


def head(dims: Dims, seed: int, dtype=None):
    """(embedding table, final norm scale, unembedding table), as
    ``params`` holds them; the unembedding is the embedding table where
    the configuration ties them."""
    embed, final = _head_fn(dims, dtype or dims.dtype)(seed_key(seed))
    return embed["table"], final, embed.get("head", embed["table"])


@functools.lru_cache(maxsize=None)
def _params_fn(dims: Dims, dtype):
    def build(key):
        keys = jax.vmap(lambda i: _layer_key(key, i))(
            jnp.arange(dims.layers))
        stack = jax.vmap(lambda k: _tree(layer_shapes(dims), k, dtype,
                                         dims.init_std))(keys)
        return {"embed": _embed(key, dims, dtype),
                "blocks": {"stack": stack},
                "final_norm": {"scale": _final_norm(key, dims, dtype)}}

    return jax.jit(build)


def params(dims: Dims, seed: int, dtype=None) -> dict:
    """Every weight in the program's layout (layers stacked on a leading
    axis), drawn in one jitted call on the default device; the seed is an
    argument, so one compiled program serves every seed."""
    return _params_fn(dims, dtype or dims.dtype)(seed_key(seed))

