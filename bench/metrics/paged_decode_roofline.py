"""Kernels (``kernels/paged_attention.py``): the least time the chip needs
for the paged decode attention the window required, over the device time
of the paged decode kernels, in percent.

Required work, fixed by the algorithm and not by the kernel: for each
decoded token that attends ``c`` positions, in every layer, the K and V of
those positions once per KV head, plus its q and its output, in the
served dtype (``decode_work``); ``4 * c * head_dim * heads`` FLOPs.  The
least time is the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth; at these shapes bytes bind (about one FLOP per byte).  A
kernel that reads pages twice or reads pages past ``pos`` does not raise
the required bytes, so it lowers the share.

One kernel body, ``_paged_decode_kernel``, serves decode: one program per
slot and split, grid ``(B, num_splits)``, each copying the pages its slot
attends once for all KV heads.  Split-K runs the same body over page
ranges and merges the partials in the dense ``_splitk_combine_kernel``.
The device time is that of the ops ``KERNELS`` matches."""
KERNELS = r"^paged_decode_attention|splitk"
ITEMSIZE = 2  # bf16


def decode_work(dims, c: int):
    """(FLOPs, bytes) of one decoded token's attention over all layers."""
    kv = 2 * c * dims.kv_heads * dims.head_dim * ITEMSIZE
    qo = 2 * dims.heads * dims.head_dim * ITEMSIZE
    return (4.0 * c * dims.head_dim * dims.heads * dims.layers,
            float(kv + qo) * dims.layers)


def least_time_s(ctx):
    flops = nbytes = 0.0
    for t in ctx.ticks:
        if ctx.w0 <= t.end < ctx.w1:
            for c in t.ctx:
                f, b = decode_work(ctx.dims, c)
                flops, nbytes = flops + f, nbytes + b
    return max(flops / ctx.peaks["bf16_flops_per_s"],
               nbytes / ctx.peaks["hbm_bytes_per_s"])


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.op_time_s(KERNELS)
    need = least_time_s(ctx)
    if busy <= 0 or need <= 0:
        return None
    return 100.0 * need / busy
