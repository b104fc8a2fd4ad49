"""KV manager (``runtime/kv_pool.py``): prompt tokens matched from the
prefix cache over prompt tokens admitted, in the window, in percent.

Both come from the program's own ``KVCacheManager.admit`` results, read
by the benchmark as each admission returns (``AdmitResult.matched``, full
pages times ``page_size``).  The registry's ``kv_prefix_hits`` counts the
same pages but also re-counts them each time an admission is refused for
lack of pages and tried again, so it is not used."""


def read(ctx):
    rows = [(p, m) for t, p, m, _ in ctx.admits if ctx.w0 <= t < ctx.w1]
    total = sum(p for p, _ in rows)
    if not total:
        return None
    return 100.0 * sum(m for _, m in rows) / total
