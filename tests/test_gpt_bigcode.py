"""The GPTBigCode block (granite-20b): LayerNorm with a bias, learned
absolute positions, a bias on every projection, MQA 48:1.

* A config that keeps the defaults (RMSNorm at 1e-6, RoPE, no biases)
  serves the same logits, bit for bit, as before the block's fields
  existed.
* granite-20b states its published block.
* The learned position table is read at each token's absolute position
  in every path the engine runs: the full forward, dense and paged
  decode, the paged prefill chunk (plain and buffered, XLA and Pallas)
  and the speculative verify block.
* Both paged kernels at one KV head under 48 query heads, in interpret
  mode, against their oracles.
"""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import (paged_decode_attention_splitk_tpu,
                                           paged_decode_attention_tpu,
                                           paged_prefill_attention_tpu)
from repro.kernels.ref import (paged_decode_attention_ref,
                               paged_prefill_attention_ref)
from repro.models import LM, RuntimeKnobs
from repro.models.layers import unembed

# sha256 of the float32 logits of a 2-layer internlm2-shaped model
# (RuntimeKnobs(cache_dtype=float32), params from PRNGKey(0)): a 32-token
# paged prefill chunk of slot 1, then one paged decode tick with slot 0
# idle and slot 1 at position 32.  Recorded on the CPU at commit
# 8676ef14a6f4fee389ab9f741d30c479272e8d5d, before ArchConfig had the norm,
# position and projection-bias fields.
PARENT_DIGESTS = {
    "prefill_chunk":
        "f6dcf43fad2357ef443738e4e814b78590812a9f34f774ba6303a4c88161c41e",
    "decode":
        "cd302526b0b42ebe13ab98b3c4a60a802ebcc12cde319c12a0008e26061c8156",
}


def _internlm2_tiny():
    cfg = dataclasses.replace(get_config("internlm2-1.8b"), num_layers=2,
                              d_model=64, num_heads=4, num_kv_heads=2,
                              head_dim=16, d_ff=128, vocab_size=256)
    return LM(cfg, RuntimeKnobs(cache_dtype=jnp.float32))


def test_a_default_block_serves_the_logits_it_served_before():
    model = _internlm2_tiny()
    assert (model.cfg.norm, model.cfg.norm_eps, model.cfg.position_embeddings,
            model.cfg.proj_bias) == ("rmsnorm", 1e-6, "rope", False)
    params = model.init(jax.random.PRNGKey(0))
    assert "pos_embed" not in params
    assert set(params["final_norm"]) == {"scale"}
    ps = 16
    caches = model.init_cache_paged(9, ps)
    table = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 256)
    pf, caches = jax.jit(model.prefill_chunk_step_paged,
                         static_argnames="page_size")(
        params, caches, toks, jnp.int32(1), jnp.int32(0), table, page_size=ps)
    dec, _ = jax.jit(model.decode_step_paged, static_argnames="page_size")(
        params, caches, jnp.asarray([[3], [7]], jnp.int32),
        jnp.asarray([-1, 32], jnp.int32), table, page_size=ps)
    for name, a in (("prefill_chunk", pf), ("decode", dec)):
        got = hashlib.sha256(np.asarray(a, np.float32).tobytes()).hexdigest()
        assert got == PARENT_DIGESTS[name], name


def test_granite_20b_states_its_published_block():
    c = get_config("granite-20b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.head_dim,
            c.d_ff, c.vocab_size) == (52, 6144, 48, 1, 128, 24576, 49152)
    assert (c.norm, c.norm_eps, c.position_embeddings, c.max_positions) == (
        "layernorm", 1e-5, "learned", 8192)
    assert c.qkv_bias and c.proj_bias and c.tie_embeddings
    assert not c.gated_mlp
    model = LM(dataclasses.replace(c, num_layers=13),
               RuntimeKnobs(param_dtype=jnp.bfloat16))
    specs = model.param_specs()
    layer = jax.tree.map(lambda a: a.shape[1:], specs["blocks"]["stack"])
    assert layer == {
        "ln1": {"scale": (6144,), "bias": (6144,)},
        "attn": {"wq": (6144, 48, 128), "bq": (48, 128),
                 "wk": (6144, 1, 128), "bk": (1, 128),
                 "wv": (6144, 1, 128), "bv": (1, 128),
                 "wo": (48, 128, 6144), "bo": (6144,)},
        "ln2": {"scale": (6144,), "bias": (6144,)},
        "mlp": {"w_up": (6144, 24576), "b_up": (24576,),
                "w_down": (24576, 6144), "b_down": (6144,)}}
    assert specs["pos_embed"]["table"].shape == (8192, 6144)
    assert set(specs["final_norm"]) == {"scale", "bias"}
    assert "head" not in specs["embed"]
    nbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(specs))
    assert 10.5e9 < nbytes < 10.6e9  # 13 layers, the table, the positions


# --------------------------------------------- positions in every path
def _gpt_bigcode_tiny(**knobs):
    cfg = dataclasses.replace(get_config("granite-20b"), num_layers=2,
                              d_model=64, num_heads=8, num_kv_heads=1,
                              head_dim=16, d_ff=128, vocab_size=256,
                              max_positions=128)
    return LM(cfg, RuntimeKnobs(cache_dtype=jnp.float32, q_chunk=16, **knobs))


def _random_params(model, seed=0, std=0.2):
    """Every leaf random (biases and norms too, so that each one counts)."""
    spec = model.param_specs()
    leaves, tree = jax.tree.flatten(spec)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        std * jax.random.normal(k, a.shape, a.dtype)
        for k, a in zip(keys, leaves)])


@pytest.mark.parametrize("use_pallas", [False, True])
def test_learned_positions_enter_every_serving_path(use_pallas):
    """Prompt of 40 tokens then 6 decoded ones, by each cached path, match
    the full forward's logits at the same positions (float32 throughout,
    so they differ by summation order alone: 1e-4)."""
    model = _gpt_bigcode_tiny(use_pallas=use_pallas)
    params = _random_params(model)
    # 48 tokens fill the forward's query blocks; 46 are served, and by
    # causality their rows ignore the last two
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (48,), 0,
                                        256), np.int32)
    x, _, _ = jax.jit(lambda p, b: model.hidden(p, b, "prefill"))(
        params, {"tokens": jnp.asarray(seq[None])})
    full = np.asarray(unembed(params["embed"], x))[0]
    p, ps, chunk = 40, 8, 16
    # the prompt's last row, then each decoded row
    want = full[p - 1:46]
    table = jnp.asarray(np.array([[0] * 8, list(range(1, 9))], np.int32))

    def paged(prefill, buf=None):
        caches = model.init_cache_paged(9, ps)
        padded = np.zeros(48, np.int32)
        padded[:p] = seq[:p]
        for c in range(0, 48, chunk):
            args = (params, caches, jnp.asarray(padded[None, c:c + chunk]),
                    jnp.int32(1), jnp.int32(c), table)
            if buf is None:
                lg, caches = prefill(*args)
            else:
                lg, caches, buf = prefill(*args, buf)
        rows = [np.asarray(lg)[(p - 1) - 32]]
        step = jax.jit(model.decode_step_paged, static_argnames="page_size")
        for t in range(p, 46):
            lg, caches = step(params, caches,
                              jnp.asarray([[0], [seq[t]]], jnp.int32),
                              jnp.asarray([-1, t], jnp.int32), table,
                              page_size=ps)
            rows.append(np.asarray(lg)[1])
        return np.stack(rows), caches

    plain = jax.jit(model.prefill_chunk_step_paged,
                    static_argnames="page_size")
    got, caches = paged(lambda *a: plain(*a, page_size=ps))
    assert np.abs(got - want).max() < 1e-4
    # the speculative verify block: the last three tokens at once
    spec = jax.jit(model.decode_step_spec_paged, static_argnames="page_size")
    lg, _ = spec(params, caches,
                 jnp.asarray([[0, 0, 0], seq[43:46]], jnp.int32),
                 jnp.asarray([-1, 43], jnp.int32), table, page_size=ps)
    assert np.abs(np.asarray(lg)[1] - full[43:46]).max() < 1e-4
    if use_pallas:
        return
    buffered = jax.jit(model.prefill_chunk_step_paged_buf,
                       static_argnames=("page_size", "gather"))
    got, _ = paged(lambda *a: buffered(*a, page_size=ps),
                   buf=model.init_cache(1, 64))
    assert np.abs(got - want).max() < 1e-4
    # the dense cache: chunked prefill of slot 0, then decode
    caches = model.init_cache(1, 64)
    pf = jax.jit(model.prefill_chunk_step)
    padded = np.zeros(48, np.int32)
    padded[:p] = seq[:p]
    for c in range(0, 48, chunk):
        lg, caches = pf(params, caches, jnp.asarray(padded[None, c:c + chunk]),
                        jnp.int32(0), jnp.int32(c))
    rows = [np.asarray(lg)[(p - 1) - 32]]
    dec = jax.jit(model.decode_step)
    for t in range(p, 46):
        lg, caches = dec(params, caches, jnp.asarray([[seq[t]]], jnp.int32),
                         jnp.asarray([t], jnp.int32))
        rows.append(np.asarray(lg)[0])
    assert np.abs(np.stack(rows) - want).max() < 1e-4


def test_the_position_table_is_read_at_absolute_positions():
    """Shifting the table by one row changes every cached path's logits
    (the rope-free block has no other position signal)."""
    model = _gpt_bigcode_tiny()
    params = _random_params(model)
    tokens = jnp.full((1, 1), 5, jnp.int32)
    step = jax.jit(model.decode_step)
    caches = model.init_cache(1, 16)
    a, caches = step(params, caches, tokens, jnp.asarray([0], jnp.int32))
    b, _ = step(params, caches, tokens, jnp.asarray([1], jnp.int32))
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-2
    shifted = dict(params, pos_embed={
        "table": jnp.roll(params["pos_embed"]["table"], -1, axis=0)})
    c, _ = step(shifted, model.init_cache(1, 16), tokens,
                jnp.asarray([0], jnp.int32))
    assert np.abs(np.asarray(c) - np.asarray(a)).max() > 1e-2


# ---------------------------------------------- kernels at MQA 48:1
def _pools(n_pages, kv, ps, d, seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.normal(size=(n_pages, kv, ps, d)), jnp.float32)
            for _ in range(2)]


@pytest.mark.parametrize("splits", [1, 2])
def test_paged_decode_kernel_at_48_query_heads_over_one(splits):
    b, h, kv, d, ps, max_pages = 3, 48, 1, 128, 16, 8
    kp, vp = _pools(1 + b * max_pages, kv, ps, d, 0)
    pt = np.arange(1, 1 + b * max_pages, dtype=np.int32).reshape(b, max_pages)
    pt = np.ascontiguousarray(pt[:, ::-1])  # pages out of order
    pos = np.array([127, -1, 40], np.int32)
    q = jnp.asarray(np.random.default_rng(1).normal(size=(b, h, 1, d)),
                    jnp.float32)
    ref = paged_decode_attention_ref(q, kp, vp, pt, pos)
    if splits == 1:
        out = paged_decode_attention_tpu(q, kp, vp, jnp.asarray(pt), pos,
                                         interpret=True)
    else:
        out = paged_decode_attention_splitk_tpu(
            q, kp, vp, jnp.asarray(pt), pos, num_splits=splits,
            interpret=True)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
    assert float(jnp.max(jnp.abs(out[1]))) == 0.0  # the idle slot


def test_paged_prefill_kernel_at_48_query_heads_over_one():
    h, kv, d, ps, max_pages, c = 48, 1, 128, 16, 8, 32
    kp, vp = _pools(1 + max_pages, kv, ps, d, 2)
    row = jnp.asarray(np.arange(max_pages, 0, -1, dtype=np.int32))
    q = jnp.asarray(np.random.default_rng(3).normal(size=(1, h, c, d)),
                    jnp.float32)
    for offset in (0, 64):
        ref = paged_prefill_attention_ref(q, kp, vp, row, offset)
        out = paged_prefill_attention_tpu(q, kp, vp, row, offset,
                                          interpret=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
