"""A block for tests only: the three things GPTBigCode's block has that the
dense block lacks, at a tiny size, with a tied head.

    h0 = table[token] + pos_embed.table[position]    (learned positions)
    x = layernorm(h) * ln1.scale + ln1.bias           (LayerNorm with bias)
    q = x Wq + bq, k = x Wk + bk, v = x Wv + bv       (biases on every
    h = h + attention(q, k, v) Wo + bo                 projection)
    h = h + gelu_tanh(layernorm(h) * ln2.scale + ln2.bias) Wup + bup) Wdown
          + bdown
    logits = (layernorm(h) * final_norm.scale + final_norm.bias) table^T

``layernorm(x) = (x - mean(x)) / sqrt(var(x) + eps)``.  The position
table and the final norm's bias are drawn at ``weights.NEXT`` and
``NEXT + 1``; ``weights.draw`` draws every leaf, so the biases are N(0,
init_std) like the matrices.  ``gated`` is the one size the metric readers
read that the block has no use for.
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
    dtype: str = "float32"
    init_std: float = W.SCALE
    gated = False  # a two-matrix MLP, as the metric readers count it

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

    def layer_shapes(self) -> dict:
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
        d = self.d_model
        return {"embed": {"table": (W.EMBED, (self.vocab, d))},
                "pos_embed": {"table": (W.NEXT, (self.positions, d))},
                "final_norm": {"scale": (W.FINAL_NORM, (d,)),
                               "bias": (W.NEXT + 1, (d,))}}

    def fp8_axes(self) -> dict:
        return {"attn/wq": 0, "attn/wk": 0, "attn/wv": 0, "attn/wo": (0, 1),
                "mlp/w_up": 0, "mlp/w_down": 0, "embed/table": 1}

    def embed(self, outer, tokens, positions):
        tok = jnp.take(outer["embed"]["table"], tokens, axis=0)
        pos = jnp.take(outer["pos_embed"]["table"], positions, axis=0,
                       mode="clip")  # the bucket's padding runs past it
        return tok.astype(jnp.float32) + pos.astype(jnp.float32)

    def layer(self, h, w, lowp: bool):
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
        f = outer["final_norm"]
        x = layernorm(h, f["scale"], f["bias"], self.eps)
        return jnp.dot(x, outer["embed"]["table"].T, precision=R.HI)

    def arch(self) -> dict:
        # the program's ArchConfig has no field for learned positions or
        # LayerNorm, so no program of this repo passes this check
        return {"family": "dense", "num_layers": self.layers,
                "d_model": self.d_model, "num_heads": self.heads,
                "num_kv_heads": self.kv_heads,
                "head_dim": self.head_dim, "d_ff": self.d_ff,
                "vocab_size": self.vocab, "gated_mlp": False,
                "qkv_bias": True, "tie_embeddings": True,
                "position_embeddings": "learned", "norm": "layernorm",
                "moe": None, "ssm": None, "input_mode": "tokens"}
