"""95th percentile of every gap between consecutive streamed tokens that
ends in the window, with each gap still open at the close counted to the
close."""
import stats


def read(ctx):
    return stats.p95_ms(stats.itl_s(ctx.recs, ctx.w0, ctx.w1))
