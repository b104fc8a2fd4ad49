"""Output tokens streamed in the window, over the window's length."""
import stats


def read(ctx):
    return stats.tokens_in(ctx.recs, ctx.w0, ctx.w1) / (ctx.w1 - ctx.w0)
