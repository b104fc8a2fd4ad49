"""Kernels (``kernels/paged_attention.py``): the least time the chip needs
for the paged prefill attention the window's admissions required, over
the device time of the paged prefill kernel, in percent.

Required work, fixed by the algorithm and not by the kernel: the engine
prefills a prompt's positions ``start..plen - 1`` in chunks of
``prefill_chunk`` rows at offsets ``start, start + C, ...``.  A chunk whose
real rows are positions ``o..e - 1`` attends keys ``0..e - 1``; in every
layer it needs the K and V of those ``e`` positions once per KV head, plus
its q and its output, in bf16 (``chunk_work``), and ``4 * head_dim *
heads`` FLOPs per causal (query, key) pair.  The least time is the larger
of FLOPs over the bf16 peak and bytes over HBM bandwidth.  A kernel that
reads a page once per query head, or pages past the chunk, or computes
the chunk's padding rows does not raise the required work, so it lowers
the share.  The device time is that of the ops ``KERNELS`` matches."""
KERNELS = r"^paged_prefill_attention"
ITEMSIZE = 2  # bf16


def chunk_work(dims, o: int, e: int):
    """(FLOPs, bytes) of the attention of a chunk whose real rows are
    positions ``o..e - 1``, over all layers."""
    pairs = (e * (e + 1) - o * (o + 1)) // 2  # sum of p + 1 over the rows
    kv = 2 * e * dims.kv_heads * dims.head_dim * ITEMSIZE
    qo = 2 * (e - o) * dims.heads * dims.head_dim * ITEMSIZE
    return (4.0 * pairs * dims.head_dim * dims.heads * dims.layers,
            float(kv + qo) * dims.layers)


def prompt_work(dims, start: int, plen: int, chunk: int):
    """(FLOPs, bytes) of prefilling positions ``start..plen - 1``."""
    flops = nbytes = 0.0
    for o in range(start, plen, chunk):
        f, b = chunk_work(dims, o, min(o + chunk, plen))
        flops, nbytes = flops + f, nbytes + b
    return flops, nbytes


def least_time_s(ctx):
    flops = nbytes = 0.0
    for t, plen, _, start in ctx.admits:
        if ctx.w0 <= t < ctx.w1:
            f, b = prompt_work(ctx.dims, start, plen,
                               ctx.engine["prefill_chunk"])
            flops, nbytes = flops + f, nbytes + b
    return max(flops / ctx.peaks["bf16_flops_per_s"],
               nbytes / ctx.peaks["hbm_bytes_per_s"])


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.op_time_s(KERNELS)
    if busy <= 0:
        return None
    need = least_time_s(ctx)
    return 100.0 * need / busy if need > 0 else None
