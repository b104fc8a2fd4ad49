"""Sharding rules: param/activation/cache -> NamedSharding on the mesh.

Axis convention (launch/mesh.py):
  "model"         — tensor parallel: attention heads, MLP hidden, experts,
                    vocab.
  "data"          — batch; with ``fsdp=True`` also shards a weight dim
                    (FSDP/ZeRO-3 style, all-gathered per layer inside scan).
  "pod" (optional)— pure data parallelism across pods; the only axis whose
                    collectives cross DCN.  Optimizer state is additionally
                    sharded over it (ZeRO-1 across pods).

Rules are name-based with a divisibility fallback chain: each candidate
PartitionSpec is tried in order and the first one where every named dim
divides the mesh axis size wins; otherwise that dim is replicated.  This is
what makes one rule set serve all 10 architectures (kv heads 1..32, experts
8/128, uneven mamba projections) without per-arch tables.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --------------------------------------------------------------- utilities
def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def _fits(mesh: Mesh, shape, spec) -> bool:
    for dim, axis in zip(shape, spec):
        if axis is not None and dim % _axis_size(mesh, axis) != 0:
            return False
    return True


def _choose(mesh: Mesh, shape, *candidates) -> P:
    """First candidate whose named axes all divide evenly; else drop axes."""
    for spec in candidates:
        if len(spec) == len(shape) and _fits(mesh, shape, spec):
            return P(*spec)
    # last resort: keep only the axes that fit, dim by dim
    spec = candidates[0] if candidates else (None,) * len(shape)
    fixed = [a if (a is not None and dim % _axis_size(mesh, a) == 0) else None
             for dim, a in zip(shape, spec)]
    return P(*fixed)


def _dp_axes(mesh: Mesh):
    axes = [a for a in ("pod", "data") if a in mesh.shape]
    if not axes:
        return None
    return tuple(axes) if len(axes) > 1 else axes[0]


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


# ----------------------------------------------------------- param rules
def _trailing_spec(pstr: str, key: str, shape, mesh, fsdp: bool,
                   zero_axis) -> P:
    """PartitionSpec for the *semantic* (trailing) dims of one param."""
    fs = zero_axis if zero_axis is not None else ("data" if fsdp else None)
    rank = len(shape)

    def c(*cands):
        return _choose(mesh, shape, *cands)

    if key in ("table", "head"):  # (V, dm); vocab may not divide (mamba2)
        # never FSDP-shard dm of the embedding: the token-gather then needs
        # an involuntary replicate-repartition EVERY microbatch (measured:
        # ~350 GB/dev/step on qwen3 multipod — §Perf H1)
        if zero_axis is None:
            fs = None
        return c(("model", fs), ("model", None), (fs, "model"),
                 (None, "model"), (None, None))
    if key == "wq":  # (dm, H, hd); head-count may not divide |model| (40H)
        return c((fs, "model", None), (None, "model", None),
                 ("model", None, None), (None, None, None))
    if key in ("wk", "wv"):  # (dm, KV, hd)
        return c((fs, "model", None), ("model", None, None),
                 (None, None, None))
    if key == "wo":  # (H, hd, dm)
        return c(("model", None, fs), ("model", None, None),
                 (None, None, "model"), (None, None, None))
    if key in ("bq", "bk", "bv"):  # (H, hd)
        return c(("model", None), (None, None))
    if "moe" in pstr:
        if key == "router":  # (dm, E)
            return P(*([None] * rank))
        if key in ("w_gate", "w_up"):  # (E, dm, dff)
            return c(("model", fs, None), ("model", None, None),
                     (None, fs, "model"), (None, None, "model"),
                     (None, None, None))
        if key == "w_down":  # (E, dff, dm)
            return c(("model", None, fs), ("model", None, None),
                     (None, "model", fs), (None, "model", None),
                     (None, None, None))
    if key in ("w_gate", "w_up"):  # mlp (dm, ff)
        return c((fs, "model"), (None, "model"), (None, None))
    if key == "b_up":  # (ff,): with w_up's columns
        return c(("model",), (None,))
    if key == "w_down":  # (ff, dm)
        return c(("model", fs), ("model", None), (None, None))
    if key == "in_proj":  # (dm, d_in)
        return c((fs, "model"), (None, "model"), (None, None))
    if key == "out_proj":  # (di, dm)
        return c(("model", fs), ("model", None), (None, None))
    # conv_w, conv_b, A_log, dt_bias, D, norm scales and biases, the other
    # biases (bo, b_down): replicate
    return P(*([None] * rank))


_SEMANTIC_RANK = {
    "table": 2, "head": 2, "wq": 3, "wk": 3, "wv": 3, "wo": 3,
    "bq": 2, "bk": 2, "bv": 2, "router": 2, "in_proj": 2, "out_proj": 2,
    "w_gate": 2, "w_up": 2, "w_down": 2,  # dense MLP (moe overrides to 3)
    "bo": 1, "b_up": 1, "b_down": 1, "bias": 1,  # proj_bias, LayerNorm
    "conv_w": 2, "conv_b": 1, "A_log": 1, "dt_bias": 1, "D": 1,
    "norm_scale": 1, "scale": 1,
}


def _param_spec(path, leaf, mesh, cfg, fsdp, zero_axis=None) -> P:
    pstr = _path_str(path)
    key = pstr.rsplit("/", 1)[-1]
    shape = leaf.shape
    if "moe" in pstr and key in ("w_gate", "w_up", "w_down"):
        rank = 3
    else:
        rank = _SEMANTIC_RANK.get(key, len(shape))
    lead = len(shape) - rank  # stacked layer dims, never sharded
    spec = _trailing_spec(pstr, key, shape[lead:], mesh, fsdp, zero_axis)
    return P(*([None] * lead + list(spec)))


def param_shardings(mesh: Mesh, cfg, param_specs, *, fsdp: bool,
                    layout: str = "tp"):
    """NamedShardings for the parameter pytree (abstract or concrete).

    layout="dp": pure data parallelism — weights replicated, every mesh
    axis used for batch (the right layout for small models on big meshes,
    where TP activation all-reduces dwarf the compute; §Perf H3).
    """
    if layout == "dp":
        return jax.tree.map(
            lambda leaf: NamedSharding(mesh, P(*([None] * len(leaf.shape)))),
            param_specs)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, _param_spec(path, leaf, mesh, cfg, fsdp)),
        param_specs)


def opt_state_shardings(mesh: Mesh, cfg, param_specs, *, fsdp: bool,
                        layout: str = "tp"):
    """Optimizer state (master + moments): ZeRO — FSDP dim extends over
    ("pod","data") when both exist, halving per-chip optimizer bytes.
    Under layout="dp" the optimizer state still shards (ZeRO-1)."""
    zero = _dp_axes(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, _param_spec(path, leaf, mesh, cfg, True, zero_axis=zero)),
        param_specs)


def grad_shardings(mesh: Mesh, cfg, param_specs):
    """Gradient-accumulator shardings (ZeRO-2) — over "data" ONLY.

    Pinning the accumulator across the pod axis makes XLA reduce every
    microbatch's grads over DCN (measured ~470 GB/dev/step on qwen3
    multipod); keeping grads data-sharded defers the pod-axis reduce to
    once per step, at the cost of pod-replicated accumulators.
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, _param_spec(path, leaf, mesh, cfg, True,
                              zero_axis="data")),
        param_specs)


def _all_axes(mesh: Mesh):
    axes = tuple(mesh.shape.keys())
    return axes if len(axes) > 1 else axes[0]


# ------------------------------------------------- serving (gather-form TP)
# Training TP (above) lets GSPMD split contraction dims and psum the
# partials — fastest, but the partial-sum order differs from the
# single-device reduction, so results drift by ~1 ulp per matmul.  Serving
# promises BITWISE-identical output on any mesh (tests pin it), so the
# serving layout uses *gather-form* tensor parallelism instead: every
# matmul whose contraction dim would be sharded keeps that operand
# replicated, and the activation feeding it is all-gathered first (the
# ``attn_out`` / ``mlp_up`` / ``moe_expert_out`` shard_fn seams in
# models/).  What IS sharded: the QKV projections and per-head attention
# over the KV cache (heads are embarrassingly parallel), the MLP up/gate
# projections (ff columns independent), per-expert MoE matmuls (expert is
# a batch dim), and the slot/batch dim over "data".  Reductions — ``wo``,
# ``w_down``, the MoE combine, rmsnorm, unembed — run replicated in the
# exact single-device order.  More all-gather traffic than psum TP; the
# memory- and FLOP-heavy half (attention reads over the KV cache, up
# projections) still scales with the mesh.


def _serve_trailing_spec(pstr: str, key: str, shape, mesh) -> P:
    def c(*cands):
        return _choose(mesh, shape, *cands)

    if key == "wq":  # (dm, H, hd): shard heads
        return c((None, "model", None), (None, None, None))
    if key in ("wk", "wv"):  # (dm, KV, hd)
        return c((None, "model", None), (None, None, None))
    if key in ("bq", "bk", "bv"):  # (H|KV, hd)
        return c(("model", None), (None, None))
    if "moe" in pstr and key in ("w_gate", "w_up", "w_down"):
        # (E, dm, dff) / (E, dff, dm): expert is a batch dim — per-expert
        # matmuls are independent, so sharding E is reduction-free
        return c(("model", None, None), (None, None, None))
    if key in ("w_gate", "w_up"):  # mlp (dm, ff): columns independent
        return c((None, "model"), (None, None))
    if key == "b_up":  # (ff,): with w_up's columns
        return c(("model",), (None,))
    # wo, w_down, router, embed table/head, the position table, norms,
    # the other biases, ssm leaves: replicated —
    # these feed (or are) the contractions that must keep reduction order
    return P(*([None] * len(shape)))


def serve_param_shardings(mesh: Mesh, cfg, param_specs):
    """Gather-form TP parameter layout for the serving engine (bitwise-
    preserving; see the block comment above)."""
    def spec(path, leaf):
        pstr = _path_str(path)
        key = pstr.rsplit("/", 1)[-1]
        shape = leaf.shape
        if "moe" in pstr and key in ("w_gate", "w_up", "w_down"):
            rank = 3
        else:
            rank = _SEMANTIC_RANK.get(key, len(shape))
        lead = len(shape) - rank  # stacked layer dims, never sharded
        tail = _serve_trailing_spec(pstr, key, shape[lead:], mesh)
        return NamedSharding(mesh, P(*([None] * lead + list(tail))))

    return jax.tree_util.tree_map_with_path(spec, param_specs)


def serve_cache_shardings(mesh: Mesh, cache_specs, *, paged: bool = False):
    """Serving-cache layout: slots (dense) or pages (paged) over "data",
    KV heads over "model"; never the sequence dim (sequence-sharded
    attention psums softmax stats, breaking bitwise identity).

    Dense attn leaves are (L..., B, S, KV, hd): B -> "data", KV ->
    "model".  Paged pool leaves are (L..., P, page_size, KV, hd): the
    page dim P -> "data" — each data row physically holds one host's
    page sub-pool, which ``runtime/kv_pool.py``'s host-local placement
    keeps slot chains inside — and KV -> "model".  SSM state/conv
    leaves shard the batch dim only (their out-projections have no
    gather seam)."""
    dp = _dp_axes(mesh)

    def spec_for(path, leaf):
        pstr = _path_str(path)
        key = pstr.rsplit("/", 1)[-1]
        shape = leaf.shape
        if key in ("k", "v", "k_scale", "v_scale"):
            # quantization scales share the pool layout with hd == 1
            # (P, page_size, KV, 1): same rule places them with their
            # pages so a page and its scale never live on different hosts
            lead = len(shape) - 4  # (B|P, S|page_size, KV, hd|1)
            base = [None] * lead
            cands = []
            if dp:
                cands.append(tuple(base) + (dp, None, "model", None))
                cands.append(tuple(base) + (dp, None, None, None))
            cands.append(tuple(base) + (None, None, "model", None))
            cands.append((None,) * len(shape))
            return _choose(mesh, shape, *cands)
        if key in ("state", "conv") and dp:
            lead = len(shape) - (4 if key == "state" else 3)
            spec = [None] * len(shape)
            spec[lead] = dp
            return _choose(mesh, shape, tuple(spec),
                           (None,) * len(shape))
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, spec_for(p, l)), cache_specs)


class ServeShardFn:
    """Activation-constraint hook for the gather-form serving layout,
    passed via ``RuntimeKnobs.shard_fn``.

    Sharding seams ("attn_q"/"attn_kv", "moe_expert_in") pin the
    parallel phases to the "model" axis; gather seams ("attn_out",
    "mlp_up", "moe_expert_out", "hidden") force the activation back
    to model-replicated immediately before a contraction over the
    sharded dim, so the contraction runs in single-device reduction
    order on every shard — the constraint that makes sharded decode
    bitwise-identical to the unsharded engine.

    Hashable on the mesh so ``RuntimeKnobs`` equality (and with it the
    ``runtime/steps.py`` compiled-step LRU) dedupes engines sharing one
    mesh."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._dp = _dp_axes(mesh)

    def __eq__(self, other):
        return isinstance(other, ServeShardFn) and self.mesh == other.mesh

    def __hash__(self):
        return hash((type(self).__name__, self.mesh))

    def __call__(self, name: str, x):
        mesh, dp = self.mesh, self._dp
        shape = x.shape
        if name in ("attn_q", "attn_kv") and len(shape) == 4:
            spec = _choose(mesh, shape, (dp, None, "model", None),
                           (dp, None, None, None), (None,) * 4)
        elif name == "attn_out" and len(shape) == 4:  # gather heads
            spec = _choose(mesh, shape, (dp, None, None, None),
                           (None,) * 4)
        elif name == "mlp_up" and len(shape) == 3:  # gather ff pre-activation
            spec = _choose(mesh, shape, (dp, None, None), (None,) * 3)
        elif name == "hidden" and len(shape) == 3:
            spec = _choose(mesh, shape, (dp, None, None), (None,) * 3)
        elif name == "moe_expert_in" and len(shape) == 5:  # shard experts
            spec = _choose(mesh, shape, (dp, None, "model", None, None),
                           (None, None, "model", None, None), (None,) * 5)
        elif name == "moe_expert_out" and len(shape) == 5:  # gather experts
            spec = _choose(mesh, shape, (dp, None, None, None, None),
                           (None,) * 5)
        else:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))


def serve_batch_sharding(mesh: Mesh, batch: int) -> Optional[NamedSharding]:
    """Sharding for the engine's per-slot host arrays (tokens, pos,
    sampling params): slot dim over "data" when divisible, else None
    (replicate — jit's default placement)."""
    dp = _dp_axes(mesh)
    if dp is None or batch % _axis_size(mesh, dp) != 0:
        return None
    return NamedSharding(mesh, P(dp))


# ------------------------------------------------------- batch/cache rules
def batch_shardings(mesh: Mesh, specs, layout: str = "tp"):
    """Inputs: shard the batch dim over (pod, data) when divisible; under
    layout="dp" the batch uses EVERY mesh axis."""
    dp = _all_axes(mesh) if layout == "dp" else _dp_axes(mesh)

    def spec_for(leaf):
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        cands = [(dp,) + (None,) * (len(shape) - 1)] if dp else []
        cands.append((None,) * len(shape))
        return _choose(mesh, shape, *cands)

    return jax.tree.map(lambda l: NamedSharding(mesh, spec_for(l)), specs)


def cache_shardings(mesh: Mesh, cache_specs):
    """KV / SSM caches.

    Layout after stacking: attn k/v (L..., B, S, KV, hd); ssm state
    (L..., B, nh, hp, ds); conv (L..., B, w, ch).  Batch goes to (pod,data)
    when divisible; otherwise the *sequence* dim of attn caches is sharded
    over "data" (sequence-parallel KV for batch-1 long-context decode).
    Head-like dims go to "model" when divisible.
    """
    dp = _dp_axes(mesh)

    def spec_for(path, leaf):
        pstr = _path_str(path)
        key = pstr.rsplit("/", 1)[-1]
        shape = leaf.shape
        if key in ("k", "v"):
            lead = len(shape) - 4  # (B, S, KV, hd)
            base = [None] * lead
            b, s, kv, hd = shape[lead:]
            cands = []
            if dp:
                # sequence-sharded KV over "model" (flash-decode style):
                # kv-head counts of 1..8 can't use the 16-way model axis,
                # but the 32k sequence always can — decode then reads only
                # S/16 per chip and psums the softmax stats (tiny).
                cands.append(tuple(base) + (dp, "model", None, None))
                cands.append(tuple(base) + (dp, None, "model", None))
                cands.append(tuple(base) + (dp, None, None, "model"))
                cands.append(tuple(base) + (dp, None, None, None))
            cands.append(tuple(base) + (None, ("data", "model"), None, None))
            cands.append(tuple(base) + (None, "data", "model", None))
            cands.append(tuple(base) + (None, "data", None, None))
            cands.append(tuple(base) + (None, None, "model", None))
            cands.append((None,) * len(shape))
            return _choose(mesh, shape, *cands)
        if key == "state":  # (L..., B, nh, hp, ds)
            lead = len(shape) - 4
            base = [None] * lead
            cands = []
            if dp:
                cands.append(tuple(base) + (dp, "model", None, None))
                cands.append(tuple(base) + (dp, None, None, None))
            cands.append(tuple(base) + (None, "model", None, None))
            cands.append((None,) * len(shape))
            return _choose(mesh, shape, *cands)
        if key == "conv":  # (L..., B, w, ch)
            lead = len(shape) - 3
            base = [None] * lead
            cands = []
            if dp:
                cands.append(tuple(base) + (dp, None, "model"))
                cands.append(tuple(base) + (dp, None, None))
            cands.append(tuple(base) + (None, None, "model"))
            cands.append((None,) * len(shape))
            return _choose(mesh, shape, *cands)
        return P(*([None] * len(shape)))

    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, spec_for(p, l)), cache_specs)


# ------------------------------------------------------- activation hooks
def make_shard_fn(mesh: Mesh, cfg, *, sp: bool = False,
                  layout: str = "tp"):
    """Activation sharding-constraint hook passed via RuntimeKnobs.

    sp=True: Megatron-style sequence parallelism — the residual stream
    (and hence every remat-saved layer boundary) is sharded over "model"
    along the sequence dim, cutting saved-activation HBM |model|-fold and
    letting grad-accum shrink (fewer FSDP regathers; §Perf H1).
    """
    dp = _all_axes(mesh) if layout == "dp" else _dp_axes(mesh)
    # under the pure-DP layout "model" already belongs to the batch axes
    tp = None if layout == "dp" else "model"

    def shard_fn(name: str, x):
        shape = x.shape
        if name == "hidden" and len(shape) == 3:  # (B, S, dm)
            if sp and tp:
                spec = _choose(mesh, shape, (dp, tp, None),
                               (dp, None, None), (None,) * 3)
            else:
                spec = _choose(mesh, shape, (dp, None, None), (None,) * 3)
        elif name == "microbatch":  # (accum, B/accum, ...)
            spec = _choose(mesh, shape,
                           (None, dp) + (None,) * (len(shape) - 2),
                           (None,) * len(shape))
        elif name in ("moe_expert_in", "moe_expert_out") and len(shape) == 5:
            # (B, n, E, C, d)
            tokens = shape[0] * shape[1] * shape[3]
            if tokens <= 4096 and tp:
                # serving regime (few tokens): weight-stationary — keep the
                # dm dim sharded over "data" on both sides of the expert
                # matmuls so the (tiny) token tensors move/reduce instead
                # of re-gathering 57 GB of FSDP-sharded expert weights
                # every decode step (§Perf H4)
                spec = _choose(mesh, shape, (None, None, tp, None, "data"),
                               (None, None, tp, None, None), (None,) * 5)
            else:
                spec = _choose(mesh, shape, (dp, None, tp, None, None),
                               (None, None, tp, None, None),
                               (dp, None, None, None, None), (None,) * 5)
        elif name == "attn_q" and len(shape) == 4:  # (B, S, H, hd)
            spec = _choose(mesh, shape, (dp, None, tp, None),
                           (None,) * 4)
        elif name == "attn_kv" and len(shape) == 4:
            spec = _choose(mesh, shape, (dp, None, tp, None),
                           (dp, None, None, None), (None,) * 4)
        else:
            return x
        if all(s is None for s in spec):
            return x  # never force replication — let XLA propagate
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return shard_fn
