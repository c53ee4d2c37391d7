"""Device time per epoch of the aggregation (forward and backward): every
operation under an ``aggregate`` scope, of any category, mean over chips.
The scope is matched as a component of the named-scope path, so the
backward's ``transpose(jvp(layer1))/aggregate`` counts.  ``None`` where
the program opens no such scope."""
import re

_SCOPE = re.compile(r"(?:^|[/(])aggregate(?:[/)]|$)")


def read(ctx):
    s = sum(ctx.trace.union_s([(o.start, o.end) for o in ops
                               if _SCOPE.search(o.name)])
            for ops in ctx.red.devices) / len(ctx.red.devices)
    return 1e3 * s / ctx.epochs if s > 0 else None
