"""The attention aggregation's share of its roofline: the least time the
chips could take for one epoch's attention work (``attention_flops`` and
``attention_bytes`` of ``bench/work``: the edge softmax and the weighted
sums, forward and backward), the larger of its FLOPs over the peak FLOP/s
and its least bytes over the peak bandwidth, over the device time of the
operations under the ``aggregate`` scope, summed over chips.  ``None``
where the model's work has no attention or the program opens no
``aggregate`` scope."""
import re

_SCOPE = re.compile(r"(?:^|[/(])aggregate(?:[/)]|$)")


def read(ctx):
    w, pk = ctx.work, ctx.peaks
    if "attention_flops" not in w:
        return None
    busy = sum(ctx.trace.union_s([(o.start, o.end) for o in ops
                                  if _SCOPE.search(o.name)])
               for ops in ctx.red.devices)
    if busy <= 0:
        return None
    least = max(w["attention_flops"] / pk["peak_flops_per_s"],
                w["attention_bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * least * ctx.epochs / busy
