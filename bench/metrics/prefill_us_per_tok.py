"""Engine steps (``runtime/steps.py``): device time of the paged
prefill-chunk executables in the traced window, over the prompt tokens
the admissions in the window left to prefill (prompt minus the chunk-
aligned cached prefix), in microseconds per token.

The executable is ``make_paged_prefill_chunk_step``'s
``prefill_chunk_step``, which XLA names ``jit_prefill_chunk_step``."""
MODULE = r"^jit_prefill_chunk_step"


def prefill_tokens(ctx) -> int:
    return sum(p - start for t, p, _, start in ctx.admits
               if ctx.w0 <= t < ctx.w1)


def read(ctx):
    n = prefill_tokens(ctx)
    if not n or ctx.trace is None:
        return None
    busy = ctx.trace.op_time_s(MODULE, modules=True)
    return busy / n * 1e6 if busy > 0 else None
