"""Production meshes + scheduler-driven submeshes.

``make_production_mesh`` builds the assigned target meshes: 16x16
("data","model") for one v5e pod (256 chips), and 2x16x16
("pod","data","model") for the 2-pod / 512-chip multi-pod dry-run.

``submesh_for_placement`` turns a Scylla placement (agent->chips) into a
Mesh over the corresponding devices — Spread puts the "pod" axis across
pods (DP over DCN), MinHost yields a single-pod mesh.  Functions, not
module constants: importing this module never touches jax device state.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from jax.sharding import AxisType, Mesh


def _mesh(device_arr, axes):
    """``Mesh`` over an explicit device array with Auto axis types."""
    return Mesh(device_arr, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_serve_mesh(shape):
    """Mesh for one sharded ``ServeEngine`` replica.

    ``shape`` is ``(data, model)`` or ``(pod, data, model)`` — the same
    axis names the serving shardings (``sharding/rules.py``'s
    ``serve_param_shardings`` / ``ServeShardFn``) key on: "model" carries
    tensor parallelism over heads/ff, the leading axes carry the decode
    slots and KV page pool ("data" hosts in the Scylla sense).  Raises if
    the product exceeds the visible device count, so a misconfigured
    ``--mesh-shape`` fails at engine construction, not first dispatch.
    """
    shape = tuple(int(s) for s in shape)
    if len(shape) not in (2, 3) or any(s < 1 for s in shape):
        raise ValueError(f"mesh shape must be (data, model) or "
                         f"(pod, data, model) of positive ints: {shape}")
    n = math.prod(shape)
    if n > len(jax.devices()):
        raise ValueError(f"mesh shape {shape} needs {n} devices, "
                         f"{len(jax.devices())} visible")
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    arr = np.array(jax.devices()[:n]).reshape(shape)
    return _mesh(arr, axes)


def make_job_mesh(n_chips: int, *, n_pods: int = 1, max_model: int = 16):
    """Mesh for a gang of ``n_chips`` (scheduler jobs, examples, tests).

    model axis = largest power-of-2 divisor up to ``max_model``; remaining
    chips become data (and pod, when the placement spans pods).
    """
    assert n_chips % n_pods == 0
    per_pod = n_chips // n_pods
    model = 1
    while model * 2 <= max_model and per_pod % (model * 2) == 0:
        model *= 2
    data = per_pod // model
    if n_pods > 1:
        return jax.make_mesh((n_pods, data, model), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def submesh_for_placement(placement, cluster, devices=None, *,
                          chips_per_host: int = 4, max_model: int = 16):
    """Build a Mesh from a Scylla placement on an actual device list."""
    devices = list(devices if devices is not None else jax.devices())
    pods = sorted({cluster.hosts[a].agent.pod_id
                   for a in placement.assignment})
    n_chips = sum(placement.assignment.values())
    n_pods = len(pods)
    if n_chips % n_pods != 0:
        n_pods = 1  # ragged across pods: treat as flat
    assert len(devices) >= n_chips, "not enough devices for the gang"
    per_pod = n_chips // n_pods
    model = 1
    while model * 2 <= max_model and per_pod % (model * 2) == 0:
        model *= 2
    data = per_pod // model
    arr = np.array(devices[:n_chips])
    if n_pods > 1:
        arr = arr.reshape(n_pods, data, model)
        return _mesh(arr, ("pod", "data", "model"))
    return _mesh(arr.reshape(data, model), ("data", "model"))
