"""A whole run off the chip (a two-layer model on the CPU, the harness's
look for a TPU skipped): ``correct`` holds for the program as it is and
comes out false when the timed path is broken underneath.

The faults a serving cell can have, each planted in the decode step the
window drives: a step that returns its KV state unchanged, half of the
slots left out of the step, and a token altered where it is produced.
One chip has no exchange between chips to leave out."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import run  # noqa: E402

# the float32 program against the float32 reference: equal to rounding
LIMITS = {"logit_gap": 1e-3, "min_tokens": 100, "max_requests": 12}


def _cell(mix="open"):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return run.Cell(
        workload={"name": f"tiny-{mix}", "chips": 1},
        config=json.loads((DATA / "tiny.json").read_text()),
        mix=json.loads((DATA / f"tiny-{mix}.json").read_text()),
        limits=LIMITS, e2e=bench["end_to_end"],
        per_layer=[m for m in bench["per_layer"]
                   if m["source"] != "device_trace"])


def _wrap_step(engine, change):
    real = engine._step

    def step(params, caches, tokens, pos, table, *rest):
        return change(real, params, caches, tokens, pos, table, *rest)

    engine._step = step


def state_unchanged(engine):
    def change(real, params, caches, tokens, pos, table, *rest):
        kept = jax.tree.map(jnp.copy, caches)
        tok, _ = real(params, caches, tokens, pos, table, *rest)
        return tok, kept
    _wrap_step(engine, change)


def half_the_slots(engine):
    """Every other live slot (the first of each pair, so a lone one too)
    is left out of the step, whichever slots the requests landed in."""
    def change(real, params, caches, tokens, pos, table, *rest):
        live = pos >= 0
        drop = live & ((jnp.cumsum(live) - 1) % 2 == 0)
        return real(params, caches, tokens, jnp.where(drop, -1, pos), table,
                    *rest)
    _wrap_step(engine, change)


def token_altered(engine):
    """Each decode step's tokens come out one id off."""
    def change(real, params, caches, tokens, pos, table, *rest):
        tok, caches = real(params, caches, tokens, pos, table, *rest)
        return (tok + 1) % 256, caches
    _wrap_step(engine, change)


def _run(fault=None, mix="open"):
    return run.run_cell(_cell(mix), 2**34 + 99, 3.0, False, jax.devices(),
                        num_pages=65, fault=fault)


@pytest.mark.parametrize("mix", ["open"])
def test_a_sound_run_is_correct(mix):
    res = _run(mix=mix)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "out_tok_s"}


@pytest.mark.parametrize("fault", [state_unchanged, half_the_slots,
                                   token_altered])
def test_a_broken_decode_step_is_not_correct(fault):
    res = _run(fault)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > LIMITS["logit_gap"]
