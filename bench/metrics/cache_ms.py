"""Device time per epoch of the JACA cache on the device: operations under
the ``cache_read`` scope (the halo's zero init, the local-tier scatter,
stale or fresh, and the global-tier read) or the ``cache_update`` scope
(the drift norms and the emitted tiers), forward and backward, mean over
chips.  ``None`` where the program opens neither scope."""
import re

_SCOPE = re.compile(r"(?:^|[/(])cache_(?:read|update)(?:[/)]|$)")


def read(ctx):
    s = sum(ctx.trace.union_s([(o.start, o.end) for o in ops
                               if _SCOPE.search(o.name)])
            for ops in ctx.red.devices) / len(ctx.red.devices)
    return 1e3 * s / ctx.epochs if s > 0 else None
