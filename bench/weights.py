"""Seeded random weights in the served layout, drawn on the device.

The benchmark makes the weights itself, from ``--seed``, and hands them to
the program; the reference regenerates the same values one layer at a time
from the same seed, so it never reads an array the program holds.  Every
leaf of layer ``l`` comes from ``fold_in(fold_in(key, LAYERS), l)`` and its
own leaf index in sorted-path order, so layer ``l`` of ``params(...)`` and
``layer(..., l)`` are the same numbers bit for bit.  Each leaf outside the
layers comes from ``fold_in(key, index)``, with the index its block gives
it: ``EMBED``, ``FINAL_NORM`` and ``UNEMBED`` for the groups every decoder
has, and ``NEXT`` onwards for a block's own (a position table, say), so
that adding a block moves no existing draw.

The leaves are the block's (``blocks/<block>.py``, whose ``Dims`` the
functions here take): ``dims.layer_shapes()`` and ``dims.outer_shapes()``.
Each leaf is drawn by ``draw`` (a norm scale 1 + N(0, ``NORM_SCALE``),
every other leaf N(0, ``dims.init_std``)), or by ``dims.draw(key, path,
shape, dtype)`` where the block has one of its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EMBED, LAYERS, FINAL_NORM, UNEMBED = 0, 1, 2, 3
NEXT = 4  # the first index free for a block's own leaves outside the layers
SCALE = 0.02  # the usual init_std: a matrix's or a bias's standard deviation
NORM_SCALE = 0.1  # the standard deviation of a norm scale around 1


def seed_key(seed: int):
    """A threefry key from all 64 bits of ``seed`` (``jax.random.key``
    keeps only the low 32)."""
    seed = int(seed) % 2**64
    data = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def draw(dims, key, path: str, shape, dtype):
    """Leaf ``path``'s values, rounded to ``dtype``: a norm scale (a path
    ending in ``scale``) is 1 + N(0, NORM_SCALE), any other N(0,
    ``dims.init_std``)."""
    z = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("scale"):
        return (1.0 + NORM_SCALE * z).astype(dtype)
    return (dims.init_std * z).astype(dtype)


def _draw(dims):
    return getattr(dims, "draw", None) or functools.partial(draw, dims)


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


def _put(tree, path: str, value):
    parts = path.split("/")
    for p in parts[:-1]:
        tree = tree.setdefault(p, {})
    tree[parts[-1]] = value


def _tree(dims, shapes: dict, key, dtype):
    """Draw every leaf of ``shapes``; leaf ``i`` in sorted-path order uses
    ``fold_in(key, i)``."""
    out: dict = {}
    draw_leaf = _draw(dims)
    for i, path in enumerate(sorted(_flatten(shapes))):
        _put(out, path, draw_leaf(jax.random.fold_in(key, i), path,
                                  _get(shapes, path.split("/")), dtype))
    return out


def _outer(dims, key, dtype):
    """Every leaf outside the layers, each from ``fold_in(key, index)``."""
    groups = dims.outer_shapes()
    out: dict = {}
    draw_leaf = _draw(dims)
    for path in _flatten(groups):
        index, shape = _get(groups, path.split("/"))
        _put(out, path, draw_leaf(jax.random.fold_in(key, index), path,
                                  shape, dtype))
    return out


def _layer_key(key, index):
    return jax.random.fold_in(jax.random.fold_in(key, LAYERS), index)


@functools.lru_cache(maxsize=None)
def _layer_fn(dims, dtype):
    return jax.jit(lambda key, index: _tree(
        dims, dims.layer_shapes(), _layer_key(key, index), dtype))


def layer(dims, seed: int, index: int, dtype=None) -> dict:
    """Layer ``index``'s weights, the same numbers as ``params``'s
    ``blocks/stack[index]`` slice."""
    return _layer_fn(dims, dtype or dims.dtype)(seed_key(seed),
                                                jnp.int32(index))


@functools.lru_cache(maxsize=None)
def _head_fn(dims, dtype):
    return jax.jit(lambda key: _outer(dims, key, dtype))


def head(dims, seed: int, dtype=None) -> dict:
    """Every weight outside the layers (the embedding and unembedding
    tables, the final norm, a block's own), as ``params`` holds them."""
    return _head_fn(dims, dtype or dims.dtype)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _params_fn(dims, dtype):
    def build(key):
        keys = jax.vmap(lambda i: _layer_key(key, i))(
            jnp.arange(dims.layers))
        stack = jax.vmap(lambda k: _tree(dims, dims.layer_shapes(), k,
                                         dtype))(keys)
        return {**_outer(dims, key, dtype), "blocks": {"stack": stack}}

    return jax.jit(build)


def params(dims, seed: int, dtype=None) -> dict:
    """Every weight in the program's layout (layers stacked on a leading
    axis), drawn in one jitted call on the default device; the seed is an
    argument, so one compiled program serves every seed."""
    return _params_fn(dims, dtype or dims.dtype)(seed_key(seed))
