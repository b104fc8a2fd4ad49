"""Model (``models/transformer.py``): model FLOPs of the tokens the decode
steps produced in the traced window, over (the decode executables' device
time x the chip's bf16 peak), in percent.

A token that attends ``c`` positions costs ``token_flops(dims, c)``: two
FLOPs per weight of every layer's projections and MLP and of the
unembedding, plus ``4 * c * head_dim * heads`` per layer for attention.
Slots that hold no request are not counted: their work is waste."""
MODULE = r"^jit_serve_step"


def token_flops(dims, c: int) -> float:
    d, hd = dims.d_model, dims.head_dim
    proj = d * hd * (2 * dims.heads + 2 * dims.kv_heads)
    mlp = (3 if dims.gated else 2) * d * dims.d_ff
    return (2.0 * (dims.layers * (proj + mlp) + dims.vocab * d)
            + 4.0 * c * hd * dims.heads * dims.layers)


def decode_flops(ctx) -> float:
    return sum(token_flops(ctx.dims, c) for t in ctx.ticks
               if ctx.w0 <= t.end < ctx.w1 for c in t.ctx)


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.op_time_s(MODULE, modules=True)
    flops = decode_flops(ctx)
    if busy <= 0 or not flops:
        return None
    return 100.0 * flops / (busy * ctx.peaks["bf16_flops_per_s"])
