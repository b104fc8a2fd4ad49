"""Size the paged KV pool from what the compiled steps need on the device.

A decode step's footprint is its arguments (weights plus the page pools)
plus its temporaries, and the temporaries grow with the pool too (on a
v5e compile the full-width internlm2-1.8b decode step holds about one more
pool copy as temporaries).  So the pool that fits is read from
``compiled.memory_analysis()`` of the real step, never from the weights
alone.  Nothing here allocates: every compile is given shapes.

    fit = fit_device_pool(model, jax.devices()[0], slots=32, max_len=2048,
                          page_size=16, chunk=32)
    ServeConfig(..., num_pages=fit.num_pages)
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.runtime.steps import compiled_step

STEP_KINDS = ("paged_serve", "paged_prefill_chunk")
HBM_SHARE = 0.9  # share of a device's bytes_limit one step may peak at


@dataclasses.dataclass(frozen=True)
class Footprint:
    """One compiled step's device memory at one pool size (bytes)."""

    kind: str
    num_pages: int
    argument: int
    temp: int
    output: int
    alias: int
    code: int
    compile_s: float
    custom_call: bool  # a Pallas kernel (tpu_custom_call) is in the program

    @property
    def peak(self) -> int:
        """Bytes live while the step runs: arguments, temporaries, the
        outputs that do not reuse a donated argument, and the program."""
        return self.argument + self.temp + self.output - self.alias \
            + self.code


@dataclasses.dataclass(frozen=True)
class PoolFit:
    num_pages: int
    budget: int
    footprints: tuple  # the verified Footprint of every step kind


def step_args(model, kind: str, *, slots: int, max_len: int, page_size: int,
              num_pages: int, chunk: int, sharding=None) -> tuple:
    """Abstract arguments of one greedy paged serving step, exactly as
    ``ServeEngine`` passes them.  ``sharding`` places them on a described
    device (compile tests); ``None`` leaves placement to jit, as the
    engine's own calls do."""
    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    params = sds(model.param_specs())
    caches = sds(jax.eval_shape(
        lambda: model.init_cache_paged(num_pages, page_size)))
    table = i32(slots, max_len // page_size)
    if kind == "paged_serve":
        return params, caches, i32(slots, 1), i32(slots), table
    if kind == "paged_prefill_chunk":
        return params, caches, i32(1, chunk), i32(), i32(), table
    raise ValueError(f"unknown step kind {kind!r} (expected {STEP_KINDS})")


def step_footprint(model, kind: str, *, num_pages: int, sharding=None,
                   **shape) -> Footprint:
    """Compile one paged serving step (the engine's own jitted callable,
    from ``runtime.steps.compiled_step``) and read its memory analysis."""
    fn = compiled_step(model, kind, page_size=shape["page_size"])
    args = step_args(model, kind, num_pages=num_pages, sharding=sharding,
                     **shape)
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    return Footprint(kind=kind, num_pages=num_pages,
                     argument=ma.argument_size_in_bytes,
                     temp=ma.temp_size_in_bytes,
                     output=ma.output_size_in_bytes,
                     alias=ma.alias_size_in_bytes,
                     code=ma.generated_code_size_in_bytes,
                     compile_s=compile_s,
                     custom_call="tpu_custom_call" in compiled.as_text())


def fit_pool_pages(model, *, slots: int, max_len: int, page_size: int,
                   chunk: int, budget: float, sharding=None,
                   log=None) -> PoolFit:
    """Largest page pool (at most the dense equivalent, ``slots *
    max_len / page_size + 1``) for which every step kind's ``peak`` stays
    within ``budget`` bytes.

    Two compiles of the decode step, at the dense equivalent and at half
    of it, give the footprint's slope per page; the pool read off that
    line is then compiled for every kind and shrunk by 2% until all fit.
    Raises ``ValueError`` when not even one slot's full page chain fits.
    """
    shape = dict(slots=slots, max_len=max_len, page_size=page_size,
                 chunk=chunk)
    max_pages = max_len // page_size
    full = slots * max_pages + 1
    log = log or (lambda msg: None)

    def footprint(kind, pages):
        fp = step_footprint(model, kind, num_pages=pages, sharding=sharding,
                            **shape)
        log(f"  {kind} @ {pages} pages: compile {fp.compile_s:.2f}s "
            f"argument {fp.argument} temp {fp.temp} peak {fp.peak} bytes")
        return fp

    hi = footprint("paged_serve", full)
    lo = footprint("paged_serve", full // 2)
    per_page = (hi.peak - lo.peak) / (full - full // 2)
    pages = min(full, int((budget - lo.peak) / per_page) + full // 2)
    while pages > max_pages:
        fps = tuple(hi if kind == "paged_serve" and pages == full
                    else footprint(kind, pages) for kind in STEP_KINDS)
        if max(fp.peak for fp in fps) <= budget:
            return PoolFit(num_pages=pages, budget=int(budget),
                           footprints=fps)
        pages = int(pages * 0.98)
    raise ValueError(f"no pool of more than {max_pages} pages fits "
                     f"{int(budget)} bytes (decode step at {full} pages "
                     f"needs {hi.peak})")


def fit_device_pool(model, device, *, log=None, **shape) -> PoolFit:
    """``fit_pool_pages`` within ``HBM_SHARE`` of ``device``'s
    ``bytes_limit``: the one pool sizing the launcher and
    ``chip_smoke.py`` share."""
    limit = device.memory_stats()["bytes_limit"]
    return fit_pool_pages(model, budget=HBM_SHARE * limit, log=log, **shape)
