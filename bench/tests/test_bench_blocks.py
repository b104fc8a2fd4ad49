"""Model blocks found by name: the dense block's weights and reference
logits as they were before blocks were files of their own, a block with
learned positions, LayerNorm biases and projection biases added as one
file, and the harness's refusals of an unknown block and of a program
whose architecture departs from its block."""
import dataclasses
import hashlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = BENCH / "tests" / "data"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import reference as R  # noqa: E402
import run  # noqa: E402
import weights as W  # noqa: E402

TINY = json.loads((DATA / "tiny.json").read_text())
DENSE = run.load_block("dense")
SEED = 2**35 + 17
PROMPTS = [((np.arange(37) * 7 + 3) % 256).astype(np.int32),
           ((np.arange(70) * 13 + 5) % 256).astype(np.int32)]

# sha256 of the drawn float32 weights (each leaf's shape, then its bytes,
# in the pytree's order) and of the reference's float32 logits at every
# position of PROMPTS, recorded on the CPU at commit
# efda54f1657664c99fce6b544446b19dda6aaac4, where the dense decoder's
# layout and forward were written into weights.py and reference.py
PARENT_DIGESTS = {
    "tiny": (
        "cc0d0d5e01f40199205c18b2c80531cb88b260c17a857ed01af42f23f827e2ff",
        "061890dd9b3e3e17dc4f522b1ecb50bd4e62d67a02d0d82df09bcfba50c26a92"),
    "tiny-untied-2mat-mqa": (
        "1b7f028a6e893c7487329da644b1c59a773399a63db4e0cb98fccec249c013a6",
        "f097a3c4c3a810012a56689c0bb6ad5c27db360314d9c7207e542dc3e0bd62dd"),
}


def _dense(variant):
    dims = DENSE.Dims.from_config(TINY["model"])
    if variant == "tiny-untied-2mat-mqa":
        dims = dataclasses.replace(dims, tied=False, gated=False, kv_heads=1)
    return dims


@pytest.mark.parametrize("variant", sorted(PARENT_DIGESTS))
def test_dense_weights_are_the_ones_drawn_before(variant):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(W.params(_dense(variant), SEED,
                                         jnp.float32)):
        a = np.asarray(leaf)
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[variant][0]


@pytest.mark.parametrize("variant", sorted(PARENT_DIGESTS))
def test_dense_reference_logits_are_the_ones_computed_before(variant):
    logits = R.logits(_dense(variant), SEED, PROMPTS,
                      [np.arange(len(p)) for p in PROMPTS])
    h = hashlib.sha256()
    for lg in logits:
        h.update(np.ascontiguousarray(lg, np.float32).tobytes())
    assert h.hexdigest() == PARENT_DIGESTS[variant][1]


# ------------------------------------------ a block added as one file
BIAS_FILE = DATA / "blocks" / "bias_ln_pos.py"
BIAS_MODEL = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 1,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 256,
              "max_position_embeddings": 128, "layer_norm_epsilon": 1e-5,
              "dtype": "float32", "init_std": 0.2}


def _load_by_path(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


BIAS = _load_by_path(BIAS_FILE, "bench_test_block_bias_ln_pos")


def _bias_dims():
    return BIAS.Dims.from_config(BIAS_MODEL)


def test_a_block_with_positions_and_biases_regenerates_bit_for_bit():
    dims = _bias_dims()
    stacked = W.params(dims, SEED)
    assert set(stacked["blocks"]["stack"]["attn"]) == {
        "wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo"}
    for i in range(dims.layers):
        one = W.layer(dims, SEED, i)
        assert jax.tree.structure(one) == jax.tree.structure(
            stacked["blocks"]["stack"])
        for a, b in zip(jax.tree.leaves(stacked["blocks"]["stack"]),
                        jax.tree.leaves(one)):
            assert np.array_equal(np.asarray(a[i]), np.asarray(b))
    outer = W.head(dims, SEED)
    assert set(outer) == {"embed", "pos_embed", "final_norm"}
    for a, b in zip(jax.tree.leaves(outer), jax.tree.leaves(
            {k: v for k, v in stacked.items() if k != "blocks"})):
        assert np.array_equal(a, b)
    # the groups every decoder has keep their draws: the same embedding
    # table and final norm scale as the dense block's at the same sizes
    dense = DENSE.Dims.from_config(TINY["model"])
    assert (dense.vocab, dense.d_model, dense.init_std) == (
        dims.vocab, dims.d_model, dims.init_std)
    was = W.head(dense, SEED, jnp.float32)
    assert np.array_equal(outer["embed"]["table"], was["embed"]["table"])
    assert np.array_equal(outer["final_norm"]["scale"],
                          was["final_norm"]["scale"])


@dataclasses.dataclass(frozen=True)
class _Shifted(BIAS.Dims):
    """The same block and weights, each token read one position on."""

    def embed(self, outer, tokens, positions):
        return super().embed(outer, tokens, positions + 1)


def test_the_reference_runs_the_block_and_reads_each_position():
    dims = _bias_dims()
    seq, rows = [np.full(24, 7, np.int32)], [np.arange(24)]
    lg = R.logits(dims, SEED, seq, rows)[0]
    assert lg.shape == (24, 256) and np.isfinite(lg).all()
    # one token repeated: with no position signal every row would be equal
    assert not np.allclose(lg[1:], lg[:1], atol=1e-3)
    shifted = R.logits(_Shifted(**dataclasses.asdict(dims)), SEED, seq,
                       rows)[0]
    assert np.abs(shifted - lg).max() > 1e-3
    low = R.logits(dims, SEED, seq, rows, lowp=True)[0]
    assert 0 < np.abs(low - lg).max()


def _harness_copy(tmp_path: Path) -> Path:
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    return tmp_path / "bench"


def test_a_new_block_is_found_from_its_file_alone(tmp_path):
    bench = _harness_copy(tmp_path)
    shutil.copy(BIAS_FILE, bench / "blocks")
    copy = _load_by_path(bench / "run.py", "bench_run_blocks_copy")
    config = {"name": "tiny-gpt", "block": "bias_ln_pos",
              "model": BIAS_MODEL}
    dims = copy.block_dims(config)
    assert type(dims).__name__ == "Dims" and dims.positions == 128
    table = W.head(dims, SEED)["pos_embed"]["table"]
    assert np.array_equal(table, W.head(_bias_dims(), SEED)["pos_embed"]
                          ["table"])


def test_the_metric_readers_take_a_new_blocks_dims():
    dims = _bias_dims()
    mfu = run.load_reader("metrics", "decode_mfu")
    roof = run.load_reader("metrics", "paged_decode_roofline")
    d, hd, c = 64, 16, 10
    proj = d * hd * (2 * 4 + 2 * 1)
    assert mfu.token_flops(dims, c) == (2.0 * (2 * (proj + 2 * d * 128)
                                               + 256 * d)
                                        + 4.0 * c * hd * 4 * 2)
    kv, qo = 2 * c * 1 * hd * 2, 2 * 4 * hd * 2
    assert roof.decode_work(dims, c) == (4.0 * c * hd * 4 * 2,
                                         float(kv + qo) * 2)


# ------------------------------------------------------------ refusals
def test_a_configuration_naming_an_unknown_block_exits_1(tmp_path):
    bench = _harness_copy(tmp_path)
    config = dict(json.loads(
        (bench / "configs" / "internlm2-1.8b.json").read_text()),
        name="nope-model", block="nope")
    (bench / "configs" / "nope-model.json").write_text(json.dumps(config))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append(dict(spec["workloads"][0], name="nope-chat",
                                  config="nope-model"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "cells" / "nope-chat.json").write_text(
        (bench / "cells" / "internlm2-chat.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(bench / "run.py"), "--workload",
                        "nope-chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       timeout=300, env=env, cwd=tmp_path)
    assert p.returncode == 1
    assert p.stdout == ""
    assert "block 'nope'" in p.stderr
    assert "known blocks: ['dense']" in p.stderr
    assert "needs a TPU" not in p.stderr  # refused before looking for one


def _tiny_program(**over):
    from repro.configs import get_config

    prog = TINY["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["overrides"])
    return dataclasses.replace(cfg, **over)


def _moe():
    from repro.configs.base import MoEConfig

    return MoEConfig(num_experts=8, experts_per_token=2, d_ff=128)


@pytest.mark.parametrize("field,value", [
    ("window", 8), ("qkv_bias", True), ("moe", "moe")])
def test_the_program_check_names_the_field_that_departs(field, value):
    dims = run.block_dims(TINY)
    run.check_program(dims, _tiny_program())
    value = _moe() if value == "moe" else value
    with pytest.raises(SystemExit) as e:
        run.check_program(dims, _tiny_program(**{field: value}))
    msg = str(e.value)
    assert f"{field}: program {value!r}, file " in msg
    assert msg.count(": program ") == 1  # the field, and no other


def test_a_field_the_program_lacks_departs():
    cfg = _tiny_program()
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    del fields["ssm"]
    with pytest.raises(SystemExit, match="ssm: program '<absent>', file None"):
        run.check_program(run.block_dims(TINY),
                          types.SimpleNamespace(**fields))
    # no program field says how positions enter or which norm runs, so
    # the GPTBigCode-like block refuses the dense program
    with pytest.raises(SystemExit, match="position_embeddings"):
        run.check_program(_bias_dims(), cfg)


def test_the_run_refuses_a_windowed_program_before_it_draws_weights():
    config = json.loads(json.dumps(TINY))
    config["program"]["overrides"]["window"] = 8
    cell = types.SimpleNamespace(config=config)
    with pytest.raises(SystemExit, match="window: program 8, file 0"):
        run.build_model(cell, "cpu")


def test_the_chat_cells_program_passes_the_check():
    cell = run.find_cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                         "internlm2-chat")
    model, dims = run.build_model(cell, "tpu")
    assert dims.layers == 24 and not dims.tied
    assert jnp.dtype(model.knobs.param_dtype) == jnp.bfloat16
