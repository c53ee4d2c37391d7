"""Device time per epoch of GAT's edge softmax (forward and backward):
every operation under an ``edge_softmax`` scope, of any category, mean
over chips.  The scope opens inside ``aggregate``, so ``aggregate_ms``
holds this time too.  ``None`` where the program opens no such scope."""
import re

_SCOPE = re.compile(r"(?:^|[/(])edge_softmax(?:[/)]|$)")


def read(ctx):
    s = sum(ctx.trace.union_s([(o.start, o.end) for o in ops
                               if _SCOPE.search(o.name)])
            for ops in ctx.red.devices) / len(ctx.red.devices)
    return 1e3 * s / ctx.epochs if s > 0 else None
