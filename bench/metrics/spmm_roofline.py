"""The aggregation's share of its roofline: the least time the chips
could take for one epoch's aggregation work (``bench/work``), the larger
of its FLOPs over the peak FLOP/s and its least bytes over the peak
bandwidth, over the device time the aggregation took, summed over chips.
The least bytes bound it on the v5e at these widths."""


def read(ctx):
    busy = ctx.trace.tagged_s(ctx.red, "aggregation") * len(ctx.red.devices)
    if busy <= 0:
        return None
    w, pk = ctx.work, ctx.peaks
    least = max(w["spmm_flops"] / pk["peak_flops_per_s"],
                w["spmm_bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least * ctx.epochs / busy
