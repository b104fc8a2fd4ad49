"""granite-20b — GPTBigCode block, MQA (kv=1), code model.

[Granite Code Models, arXiv:2405.04324, Table 1; hf
ibm-granite/granite-20b-code-base, model_type gpt_bigcode]  52L
d_model=6144 48H over one KV head (MQA) head_dim=128 d_ff=24576
vocab=49152; LayerNorm with bias (eps 1e-5) before attention, before the
MLP and at the end; 8,192 learned absolute positions added to the token
embedding (no RoPE); a bias on every projection (q, k, v, o, MLP up and
down); two-matrix MLP with tanh-GELU; LM head tied to the token embedding
(GPTBigCode's default).
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="granite-20b",
    family="dense",
    num_layers=52,
    d_model=6144,
    num_heads=48,
    num_kv_heads=1,  # MQA
    head_dim=128,
    d_ff=24576,
    vocab_size=49152,
    qkv_bias=True,
    proj_bias=True,
    gated_mlp=False,  # 2-matrix MLP, gelu_pytorch_tanh
    norm="layernorm",
    norm_eps=1e-5,
    position_embeddings="learned",
    max_positions=8192,
    tie_embeddings=True,
    supports_long_context=False,
    notes="GPTBigCode, MQA, code",
)
