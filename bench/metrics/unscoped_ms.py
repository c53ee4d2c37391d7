"""Device time per epoch of the operations under none of the training
step's phase scopes (``layer<i>``, ``tier_pull_*``, ``refresh_ring_*``,
``cache_read``, ``cache_update``, ``loss``, ``grad_sync``, ``optimizer``):
what no other scope-reading metric can attribute, mean over chips.  0
means every operation has a phase."""
import re

_PHASE = re.compile(r"(?:^|[/(])(?:layer\d+|tier_pull_[^/()]*|"
                    r"refresh_ring_[^/()]*|cache_read|cache_update|loss|"
                    r"grad_sync|optimizer)(?:[/)]|$)")


def read(ctx):
    s = sum(ctx.trace.union_s([(o.start, o.end) for o in ops
                               if not _PHASE.search(o.name)])
            for ops in ctx.red.devices) / len(ctx.red.devices)
    return 1e3 * s / ctx.epochs
