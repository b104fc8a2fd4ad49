"""Engine steps (``runtime/steps.py``): device time of the decode
executables in the traced window over the window's decode ticks, in ms.

The executable is ``make_paged_serve_step``'s ``serve_step`` (every
split-K variant too), which XLA names ``jit_serve_step``.  A decode tick
is an engine tick in which some request received a decode token."""
MODULE = r"^jit_serve_step"


def decode_ticks(ctx) -> int:
    return sum(1 for t in ctx.ticks if t.ctx and ctx.w0 <= t.end < ctx.w1)


def read(ctx):
    n = decode_ticks(ctx)
    if not n or ctx.trace is None:
        return None
    busy = ctx.trace.op_time_s(MODULE, modules=True)
    return busy / n * 1e3 if busy > 0 else None
