"""Model (``models/transformer.py``, the whole step): model FLOPs of the
prompt tokens prefilled and the tokens decoded in the traced window, over
(the device time of the prefill-chunk and decode executables x the chip's
bf16 peak), in percent.

The prompt tokens are those the window's admissions left to prefill
(prompt minus the chunk-aligned cached prefix, as ``prefill_us_per_tok``
counts them); the one at position ``p`` attends ``p + 1`` positions and
costs ``decode_mfu.token_flops(dims, p + 1)``.  Each token the window's
decode ticks produced costs ``token_flops(dims, c)`` for the ``c``
positions it attends.  The padding rows of a prompt's last chunk and the
slots that hold no request are waste and are not counted.  This is the
share of the chip's peak that the served model's arithmetic takes of the
steps' device time: a gain in any layer of the step moves it."""
MODULES = r"^jit_prefill_chunk_step|^jit_serve_step"


def step_flops(ctx) -> float:
    mfu = ctx.load("decode_mfu")
    prefill = sum(mfu.token_flops(ctx.dims, p + 1)
                  for t, plen, _, start in ctx.admits
                  if ctx.w0 <= t < ctx.w1 for p in range(start, plen))
    return prefill + mfu.decode_flops(ctx)


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.op_time_s(MODULES, modules=True)
    flops = step_flops(ctx)
    if busy <= 0 or not flops:
        return None
    return 100.0 * flops / (busy * ctx.peaks["bf16_flops_per_s"])
