"""Compile the serving kernels and the full-width decode step for a TPU v5e.

No chip is used: ``jax.experimental.topologies`` describes a v5e:2x2 host
and the TPU compiler installed with libtpu compiles for one of its chips.
This catches what interpret mode cannot — a tile Mosaic refuses, too much
VMEM, a step that does not fit one chip's HBM.  Widths are internlm2-1.8b's
(16 query / 8 KV heads, head dim 128) with 16-token pages.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and pytest-xdist workers import every test
file.  Kernel cases pass ``interpret=False``; the full-step case steers
``repro.kernels.ops._on_tpu`` (the test process's backend is the CPU).
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

V5E_HBM = 16 * 2 ** 30  # one TPU v5e chip (Google Cloud, "TPU v5e")
B, H, KV, D, PS = 8, 16, 8, 128, 16
MAX_PAGES, POOL = 128, 1025


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_args(name, sds, b=B, n_pool=POOL):
    i32, bf16, f32 = jnp.int32, jnp.bfloat16, jnp.float32
    pool = (n_pool, PS, KV, D)
    table, pos = sds((b, MAX_PAGES), i32), sds((b,), i32)
    if name == "paged_decode_chat":  # the chat cell's engine and pool
        return _kernel_args("paged_decode", sds, b=30, n_pool=3361)
    if name.startswith("paged_decode"):
        tq = 4 if name.endswith("tq4") else 1
        splits = 4 if "splitk" in name else 1
        dt = jnp.int8 if "int8" in name else bf16
        args = [sds((b, tq, H, D), bf16), sds(pool, dt), sds(pool, dt),
                table, pos]
        if dt == bf16:
            def fn(q, k, v, t, p):
                return ops.paged_decode_attention(q, k, v, t, p,
                                                  num_splits=splits,
                                                  interpret=False)
            return fn, args

        def fn(q, k, v, t, p, ks, vs):
            return ops.paged_decode_attention(q, k, v, t, p, k_scale=ks,
                                              v_scale=vs, num_splits=splits,
                                              interpret=False)
        return fn, args + [sds((n_pool, PS, KV, 1), f32)] * 2
    if name == "paged_prefill":
        def fn(q, k, v, t, slot, off):
            return ops.paged_prefill_attention(q, k, v, t, slot, off,
                                               interpret=False)
        return fn, [sds((1, 32, H, D), bf16), sds(pool, bf16),
                    sds(pool, bf16), table, sds((), i32), sds((), i32)]
    if name == "dense_decode":
        def fn(q, k, v, p):
            return ops.decode_attention(q, k, v, p, block_k=512,
                                        interpret=False)
        return fn, [sds((B, 1, H, D), bf16), sds((B, 2048, KV, D), bf16),
                    sds((B, 2048, KV, D), bf16), pos]
    if name == "flash":
        def fn(q, k, v):
            return ops.flash_attention(q, k, v, causal=True, block_q=512,
                                       block_k=512, interpret=False)
        return fn, [sds((1, 512, H, D), bf16), sds((1, 512, KV, D), bf16),
                    sds((1, 512, KV, D), bf16)]
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "paged_decode", "paged_decode_tq4", "paged_decode_int8",
    "paged_decode_splitk", "paged_decode_int8_splitk", "paged_prefill",
    "dense_decode", "flash", "paged_decode_chat"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_args(name, sds)
    _compile(fn, *args)


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_full_width_paged_step_fits_one_v5e(one_chip, monkeypatch):
    """The pool ``chip_smoke.py`` would size for a 16 GiB chip: both paged
    step kinds compile with their Pallas kernels and peak under it."""
    from repro.configs import get_config
    from repro.launch.pool_fit import HBM_SHARE, fit_pool_pages
    from repro.launch.serve import serving_knobs
    from repro.models import LM

    smoke = _chip_smoke()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    model = LM(get_config(smoke.ARCH), serving_knobs("tpu", sharded=False))
    fit = fit_pool_pages(model, slots=smoke.SLOTS, max_len=smoke.MAX_LEN,
                         page_size=smoke.PAGE_SIZE, chunk=smoke.CHUNK,
                         budget=HBM_SHARE * V5E_HBM, sharding=one_chip)
    assert fit.num_pages > smoke.MAX_LEN // smoke.PAGE_SIZE
    for fp in fit.footprints:
        assert fp.custom_call, fp.kind
        assert fp.argument + fp.temp < V5E_HBM, fp
