"""GPTBigCode's block (granite-20b-code): its weights, its float32
reference and the program check, as the benchmark draws, runs and checks
them.

A configuration file names this block with ``"block": "gpt_bigcode"``;
its ``model`` group gives the sizes (``Dims.from_config``).  For tokens at
positions 0..S-1,

    h0 = table[token] + pos_embed.table[position]     learned absolute
                                                      positions, no RoPE
and one layer is

    x = layernorm(h) * ln1.scale + ln1.bias
    q = x Wq + bq  (S, heads, head_dim)     k = x Wk + bk, v = x Wv + bv
                                            (S, kv_heads, head_dim)
    h = h + attention(q, k, v) Wo + bo      causal MQA/GQA, scale
                                            head_dim ** -0.5, f32 softmax
    h = h + gelu_tanh(x2 Wup + bup) Wdown + bdown,
        x2 = layernorm(h) * ln2.scale + ln2.bias

with ``layernorm(x) = (x - mean(x)) / sqrt(var(x) + eps)`` over the hidden
axis.  The logits are ``(layernorm(h) * final_norm.scale +
final_norm.bias)`` times the embedding table (the head is tied).

This is the block of ibm-granite/granite-20b-code-base (``model_type:
gpt_bigcode``; Granite Code Models, arXiv:2405.04324, Table 1) as
published: 48 query heads over one KV head, LayerNorm at eps 1e-5, a bias
on every projection, ``gelu_pytorch_tanh``, 8,192 learned positions.
Departures: the weights are random (below), and the tie of the head to
the embedding is GPTBigCode's default rather than read from the model's
own config, which is not in the repo (the configuration file lists both
under ``assumed``).  Attention dropout and residual dropout are zero at
inference and are left out.

Weights: ``weights.draw``'s, every matrix and every bias (the norms'
too) N(0, init_std), every norm scale 1 + N(0, 0.1), rounded to the
served dtype.  The position table is drawn at ``weights.NEXT`` and the
final norm's bias at ``NEXT + 1``, so no draw of another block moves.  The
control rounds every weight matrix and the tied table (``fp8_axes``), and
every cached key and value, to float8 e4m3, the precision below the bf16
the configuration serves in.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reference as R
import weights as W


def layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _proj(x, w, b):
    return jnp.einsum("sd,dhk->shk", x, w, precision=R.HI) + b


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    positions: int
    eps: float
    dtype: str = "bfloat16"  # the weights' served dtype
    init_std: float = W.SCALE  # standard deviation of matrices and biases
    gated = False  # a two-matrix MLP, as the metric readers count it
    tied = True  # the head is the embedding table

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        return cls(layers=model["num_hidden_layers"],
                   d_model=model["hidden_size"],
                   heads=model["num_attention_heads"],
                   kv_heads=model["num_key_value_heads"],
                   head_dim=model["head_dim"],
                   d_ff=model["intermediate_size"],
                   vocab=model["vocab_size"],
                   positions=model["max_position_embeddings"],
                   eps=float(model["layer_norm_epsilon"]),
                   dtype=model["dtype"],
                   init_std=float(model.get("init_std", W.SCALE)))

    # ------------------------------------------------------------ weights
    def layer_shapes(self) -> dict:
        """Leaf shapes of one layer, in the program's param layout."""
        d, hd, h, kv = self.d_model, self.head_dim, self.heads, self.kv_heads
        return {"ln1": {"scale": (d,), "bias": (d,)},
                "attn": {"wq": (d, h, hd), "bq": (h, hd),
                         "wk": (d, kv, hd), "bk": (kv, hd),
                         "wv": (d, kv, hd), "bv": (kv, hd),
                         "wo": (h, hd, d), "bo": (d,)},
                "ln2": {"scale": (d,), "bias": (d,)},
                "mlp": {"w_up": (d, self.d_ff), "b_up": (self.d_ff,),
                        "w_down": (self.d_ff, d), "b_down": (d,)}}

    def outer_shapes(self) -> dict:
        """The leaves outside the layers, each as (fold-in index, shape)."""
        d = self.d_model
        return {"embed": {"table": (W.EMBED, (self.vocab, d))},
                "pos_embed": {"table": (W.NEXT, (self.positions, d))},
                "final_norm": {"scale": (W.FINAL_NORM, (d,)),
                               "bias": (W.NEXT + 1, (d,))}}

    def fp8_axes(self) -> dict:
        """The weights the control rounds to fp8, each with the axes its
        product reduces over."""
        return {"attn/wq": 0, "attn/wk": 0, "attn/wv": 0, "attn/wo": (0, 1),
                "mlp/w_up": 0, "mlp/w_down": 0, "embed/table": 1}

    # ---------------------------------------------------------- reference
    def embed(self, outer, tokens, positions):
        """Hidden states (S, d_model) in float32: each token's row plus its
        position's row."""
        tok = jnp.take(outer["embed"]["table"], tokens, axis=0)
        pos = jnp.take(outer["pos_embed"]["table"], positions, axis=0,
                       mode="clip")  # the bucket's padding runs past it
        return tok.astype(jnp.float32) + pos.astype(jnp.float32)

    def layer(self, h, w, lowp: bool):
        """One layer on float32 hidden states and weights."""
        a, m = w["attn"], w["mlp"]
        x = layernorm(h, w["ln1"]["scale"], w["ln1"]["bias"], self.eps)
        q, k, v = (_proj(x, a["wq"], a["bq"]), _proj(x, a["wk"], a["bk"]),
                   _proj(x, a["wv"], a["bv"]))
        k, v = R.cached(k, lowp), R.cached(v, lowp)
        h = h + jnp.einsum("shk,hkd->sd", R.attention(q, k, v), a["wo"],
                           precision=R.HI) + a["bo"]

        def mlp(xb):
            hid = jax.nn.gelu(jnp.dot(xb, m["w_up"], precision=R.HI)
                              + m["b_up"], approximate=True)
            return jnp.dot(hid, m["w_down"], precision=R.HI) + m["b_down"]

        return h + R.row_blocks(mlp, layernorm(h, w["ln2"]["scale"],
                                               w["ln2"]["bias"], self.eps))

    def head(self, outer, h):
        """Logits (rows, vocab) of float32 hidden states ``h``."""
        f = outer["final_norm"]
        x = layernorm(h, f["scale"], f["bias"], self.eps)
        return jnp.dot(x, outer["embed"]["table"].T, precision=R.HI)

    # ------------------------------------------------------------ program
    def arch(self) -> dict:
        """Each ``ArchConfig`` field that changes the mathematics, at the
        value this block and its file state."""
        return {"family": "dense", "num_layers": self.layers,
                "d_model": self.d_model, "num_heads": self.heads,
                "num_kv_heads": self.kv_heads,
                "head_dim": self.head_dim, "d_ff": self.d_ff,
                "vocab_size": self.vocab, "gated_mlp": False,
                "norm": "layernorm", "norm_eps": self.eps,
                "position_embeddings": "learned",
                "max_positions": self.positions,
                "qkv_bias": True, "proj_bias": True,
                "tie_embeddings": True,
                "window": 0, "local_window": 0, "local_global_period": 0,
                "shared_attn_period": 0, "moe": None, "ssm": None,
                "input_mode": "tokens"}
