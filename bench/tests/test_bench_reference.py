"""The plain reference against the program's prefill-then-decode logits,
at a small size on the CPU, and the seeded weights it regenerates."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import reference as R  # noqa: E402
import weights as W  # noqa: E402

TINY = json.loads((BENCH / "tests" / "data" / "tiny.json").read_text())
CHAT_LIMITS = json.loads(
    (BENCH / "cells" / "internlm2-chat.json").read_text())["limits"]
SEED = 2**35 + 17


def _dims(**over):
    return dataclasses.replace(W.Dims.from_config(TINY["model"]), **over)


def _program(dims):
    from repro.configs import get_config
    from repro.models import LM, RuntimeKnobs

    cfg = dataclasses.replace(
        get_config(TINY["program"]["arch"]), num_layers=dims.layers,
        d_model=dims.d_model, num_heads=dims.heads,
        num_kv_heads=dims.kv_heads, head_dim=dims.head_dim, d_ff=dims.d_ff,
        vocab_size=dims.vocab, gated_mlp=dims.gated,
        tie_embeddings=dims.tied, rope_theta=dims.rope_theta)
    return LM(cfg, RuntimeKnobs(cache_dtype=jnp.float32))


def test_layers_regenerate_bit_for_bit():
    dims = _dims()
    stacked = W.params(dims, SEED, jnp.float32)
    for i in range(dims.layers):
        one = W.layer(dims, SEED, i, jnp.float32)
        for a, b in zip(jax.tree.leaves(stacked["blocks"]["stack"]),
                        jax.tree.leaves(one)):
            assert np.array_equal(np.asarray(a[i]), np.asarray(b))
    table, scale, unembed = W.head(dims, SEED, jnp.float32)
    assert np.array_equal(table, stacked["embed"]["table"])
    assert np.array_equal(scale, stacked["final_norm"]["scale"])
    assert unembed is table and "head" not in stacked["embed"]
    untied = _dims(tied=False)
    table, _, unembed = W.head(untied, SEED, jnp.float32)
    assert np.array_equal(unembed, W.params(untied, SEED, jnp.float32)
                          ["embed"]["head"])
    assert not np.array_equal(unembed, table)
    other = W.params(dims, SEED + 2**32, jnp.float32)
    assert not np.array_equal(other["embed"]["table"],
                              stacked["embed"]["table"])


def _served_logits(model, params, prompt, n_new, chunk=32, max_len=128):
    """Chunked prefill then greedy decode through the dense cache, as the
    engine runs them; the logits that chose each new token."""
    caches = model.init_cache(1, max_len)
    prefill = jax.jit(model.prefill_chunk_step)
    decode = jax.jit(model.decode_step)
    p = len(prompt)
    padded = np.zeros(-(-p // chunk) * chunk, np.int32)
    padded[:p] = prompt
    for c in range(0, len(padded), chunk):
        lg, caches = prefill(
            params, caches, jnp.asarray(padded[None, c:c + chunk]),
            jnp.int32(0), jnp.int32(c))
    rows = [np.asarray(lg)[(p - 1) - (len(padded) - chunk)]]
    out = [int(rows[0].argmax())]
    for k in range(1, n_new):
        lg, caches = decode(
            params, caches, jnp.asarray([[out[-1]]], jnp.int32),
            jnp.asarray([p + k - 1], jnp.int32))
        rows.append(np.asarray(lg)[0])
        out.append(int(rows[-1].argmax()))
    return np.stack(rows), out


@pytest.mark.parametrize("gated,kv_heads,tied,theta", [
    (True, 2, True, 1e4), (False, 1, True, 1e4), (True, 2, False, 1e6)])
def test_reference_agrees_with_prefill_then_decode(gated, kv_heads, tied,
                                                  theta):
    dims = _dims(gated=gated, kv_heads=kv_heads, tied=tied, rope_theta=theta)
    model = _program(dims)
    params = W.params(dims, SEED, jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, dims.vocab, n).astype(np.int32)
               for n in (37, 70)]
    got, outs = zip(*[_served_logits(model, params, p, 12) for p in prompts])
    seqs, rows = R.served_rows(prompts, outs)
    with jax.default_matmul_precision("highest"):
        ref = R.logits(dims, SEED, seqs, rows)
    for g, r in zip(got, ref):
        assert np.abs(g - r).max() < 1e-4
    assert max(float(x.max()) for x in R.gaps(ref, outs)) < 1e-4


def test_the_control_fails_the_cells_own_check():
    """fp8 weights and KV put a different token first at some positions,
    far enough below the reference's best to fail the chat cell's limit
    through the harness's own ``judge``, where the float32 program passes
    it.  (At d_model 64 the embedding's own token wins every position by
    a wide margin, so the model here is wider.)"""
    dims = _dims(layers=4, d_model=512, head_dim=128, d_ff=1024, vocab=4096,
                 tied=False, rope_theta=1e6)
    model = _program(dims)
    params = W.params(dims, SEED, jnp.float32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, dims.vocab, 60).astype(np.int32)
               for _ in range(8)]
    outs = [_served_logits(model, params, p, 40)[1] for p in prompts]
    seqs, rows = R.served_rows(prompts, outs)
    ref = R.logits(dims, SEED, seqs, rows)
    low = R.logits(dims, SEED, seqs, rows, lowp=True)
    run = _load_run()
    ok, checks = run.judge(ref, outs, CHAT_LIMITS, 0, 0)
    assert ok and checks["logit_gap"]["value"] < 1e-4, checks
    ok, checks = run.judge(ref, [lg.argmax(axis=1) for lg in low],
                           CHAT_LIMITS, 0, 0)
    assert not ok, checks
    assert checks["logit_gap"]["value"] > CHAT_LIMITS["logit_gap"]


def _load_run():
    import importlib.util
    spec = importlib.util.spec_from_file_location("bench_run_ref",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
