"""The repo's dense decoder block: its weights, its float32 reference and
the program check, as the benchmark draws, runs and checks them.

A configuration file names this block with ``"block": "dense"``; its
``model`` group gives the sizes (``Dims.from_config``).  For hidden states
``h`` (S, d_model) at positions 0..S-1, one layer is

    x = rmsnorm(h) * ln1.scale
    q = rope(x Wq)  (S, heads, head_dim)     k = rope(x Wk), v = x Wv
                                             (S, kv_heads, head_dim)
    h = h + attention(q, k, v) Wo            causal GQA/MQA, scale
                                             head_dim ** -0.5, f32 softmax
    h = h + mlp(rmsnorm(h) * ln2.scale)
    mlp(x) = (silu(x Wgate) * (x Wup)) Wdown      gated_mlp (SwiGLU)
    mlp(x) = gelu_tanh(x Wup) Wdown               otherwise

with ``rmsnorm(x) = x / sqrt(mean(x ** 2) + eps)`` and ``rope`` the
split-half rotation by ``pos * theta ** (-2i / head_dim)``.  The input is
``table[token]``; the logits are ``rmsnorm(h) * final_norm.scale`` times
the unembedding, a table of its own (``embed/head``) or the embedding
table where the configuration ties them.

This is InternLM2's block (arXiv:2403.17297) as published, but for the
norm's eps, which the program fixes at 1e-6 (``rms_norm_eps`` under the
configuration's ``reduced``).  A non-gated MLP takes the tanh form of
GELU.  A model whose block differs (LayerNorm, learned positions, biases
on the projections) takes a block file of its own.

Weights: ``weights.draw``'s, every matrix N(0, init_std) (0.02 unless
the configuration says), every norm scale 1 + N(0, 0.1), rounded to the
served dtype.  The control rounds every weight matrix (``fp8_axes``) and
every cached key and value to float8 e4m3, the precision below the bf16
the configurations serve in.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import reference as R
import weights as W


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x (S, H, hd) at positions 0..S-1, split-half rotation."""
    s, _, hd = x.shape
    freqs = jnp.asarray(1.0 / (theta ** (np.arange(0, hd, 2) / hd)),
                        jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    rope_theta: float
    eps: float
    tied: bool = False  # the unembedding is the embedding table
    dtype: str = "bfloat16"  # the weights' served dtype
    init_std: float = W.SCALE  # standard deviation of every matrix

    @classmethod
    def from_config(cls, model: dict) -> "Dims":
        return cls(layers=model["num_hidden_layers"],
                   d_model=model["hidden_size"],
                   heads=model["num_attention_heads"],
                   kv_heads=model["num_key_value_heads"],
                   head_dim=model["head_dim"],
                   d_ff=model["intermediate_size"],
                   vocab=model["vocab_size"],
                   gated=model["gated_mlp"],
                   rope_theta=float(model["rope_theta"]),
                   eps=float(model["rms_norm_eps"]),
                   tied=bool(model["tie_word_embeddings"]),
                   dtype=model["dtype"],
                   init_std=float(model.get("init_std", W.SCALE)))

    # ------------------------------------------------------------ weights
    def layer_shapes(self) -> dict:
        """Leaf shapes of one layer, in the program's param layout."""
        d, hd = self.d_model, self.head_dim
        mlp = {"w_up": (d, self.d_ff), "w_down": (self.d_ff, d)}
        if self.gated:
            mlp["w_gate"] = (d, self.d_ff)
        return {"ln1": {"scale": (d,)},
                "attn": {"wq": (d, self.heads, hd),
                         "wk": (d, self.kv_heads, hd),
                         "wv": (d, self.kv_heads, hd),
                         "wo": (self.heads, hd, d)},
                "ln2": {"scale": (d,)},
                "mlp": mlp}

    def outer_shapes(self) -> dict:
        """The leaves outside the layers, each as (fold-in index, shape)."""
        table = (self.vocab, self.d_model)
        embed = {"table": (W.EMBED, table)}
        if not self.tied:
            embed["head"] = (W.UNEMBED, table)
        return {"embed": embed,
                "final_norm": {"scale": (W.FINAL_NORM, (self.d_model,))}}

    def fp8_axes(self) -> dict:
        """The weights the control rounds to fp8, each with the axes its
        product reduces over."""
        head = "embed/table" if self.tied else "embed/head"
        return {"attn/wq": 0, "attn/wk": 0, "attn/wv": 0, "attn/wo": (0, 1),
                "mlp/w_gate": 0, "mlp/w_up": 0, "mlp/w_down": 0, head: 1}

    # ---------------------------------------------------------- reference
    def embed(self, outer, tokens, positions):
        """Hidden states (S, d_model) in float32 of ``tokens``; the
        positions enter through ``rope`` in each layer."""
        return jnp.take(outer["embed"]["table"], tokens,
                        axis=0).astype(jnp.float32)

    def layer(self, h, w, lowp: bool):
        """One layer on float32 hidden states and weights."""
        a = w["attn"]
        x = rmsnorm(h, w["ln1"]["scale"], self.eps)
        q = rope(jnp.einsum("sd,dhk->shk", x, a["wq"], precision=R.HI),
                 self.rope_theta)
        k = rope(jnp.einsum("sd,dhk->shk", x, a["wk"], precision=R.HI),
                 self.rope_theta)
        v = jnp.einsum("sd,dhk->shk", x, a["wv"], precision=R.HI)
        k, v = R.cached(k, lowp), R.cached(v, lowp)
        h = h + jnp.einsum("shk,hkd->sd", R.attention(q, k, v), a["wo"],
                           precision=R.HI)
        return h + R.row_blocks(self._mlp(w["mlp"]),
                                rmsnorm(h, w["ln2"]["scale"], self.eps))

    def _mlp(self, w):
        def rows(xb):
            if self.gated:
                hid = (jax.nn.silu(jnp.dot(xb, w["w_gate"], precision=R.HI))
                       * jnp.dot(xb, w["w_up"], precision=R.HI))
            else:
                hid = jax.nn.gelu(jnp.dot(xb, w["w_up"], precision=R.HI),
                                  approximate=True)
            return jnp.dot(hid, w["w_down"], precision=R.HI)

        return rows

    def head(self, outer, h):
        """Logits (rows, vocab) of float32 hidden states ``h``."""
        table = outer["embed"].get("head", outer["embed"]["table"])
        x = rmsnorm(h, outer["final_norm"]["scale"], self.eps)
        return jnp.dot(x, table.T, precision=R.HI)

    # ------------------------------------------------------------ program
    def arch(self) -> dict:
        """Each ``ArchConfig`` field that changes the mathematics, at the
        value this block and its file state."""
        return {"family": "dense", "num_layers": self.layers,
                "d_model": self.d_model, "num_heads": self.heads,
                "num_kv_heads": self.kv_heads,
                "head_dim": self.head_dim, "d_ff": self.d_ff,
                "vocab_size": self.vocab, "gated_mlp": self.gated,
                "rope_theta": self.rope_theta,
                "tie_embeddings": self.tied, "qkv_bias": False,
                "window": 0, "local_window": 0, "local_global_period": 0,
                "shared_attn_period": 0, "moe": None, "ssm": None,
                "input_mode": "tokens"}
