"""Each one-chip cell's engine shape fits a TPU v5e through the program's
own pool sizing, compiled for a described chip (no chip is used).

``fit_pool_pages`` compiles the decode step at the dense-equivalent pool
first; where the TPU compiler refuses that program for memory, the fit
raises and the benchmark's run exits before it serves a request.  This
compiles what a run's set-up would, for every cell in ``BENCHMARK.json``,
so a configuration or engine shape that the fit cannot size fails here.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and pytest-xdist workers import every test
file.  ``repro.kernels.ops._on_tpu`` is steered, as the test process's
backend is the CPU.
"""
import json
import sys
from pathlib import Path

import jax
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run  # noqa: E402

BYTES_LIMIT = 16_909_336_064  # one v5e's memory_stats()["bytes_limit"]
BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
ONE_CHIP_CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.fixture(scope="module")
def one_chip():
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
        yield SingleDeviceSharding(desc.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("workload", ONE_CHIP_CELLS)
def test_the_cells_pool_fits_one_v5e(workload, one_chip, monkeypatch):
    from repro.kernels import ops
    from repro.launch.pool_fit import HBM_SHARE, fit_pool_pages

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cell = run.find_cell(BENCH, workload)
    model, _ = run.build_model(cell, "tpu")
    eng = cell.config["engine"]
    fit = fit_pool_pages(model, slots=eng["slots"], max_len=eng["max_len"],
                         page_size=eng["page_size"],
                         chunk=eng["prefill_chunk"],
                         budget=HBM_SHARE * BYTES_LIMIT, sharding=one_chip)
    assert fit.num_pages > eng["max_len"] // eng["page_size"]
    for fp in fit.footprints:
        assert fp.custom_call, fp.kind  # the Pallas kernels are in the step
        assert fp.peak <= HBM_SHARE * BYTES_LIMIT, fp
