"""Device time per epoch in which a collective runs on a chip and no other
operation does, mean over the chips: the exchange and the gradient sum
that compute does not hide.  Collectives are the collective-permutes (the
halo rings), all-reduces (the gradient ``psum``), all-gathers,
reduce-scatters and all-to-alls, with their asynchronous start and done
parts, whether the HLO names the collective in its opcode or wraps it in
``async-start`` / ``async-done`` (then the instruction's name says which).
The exposed part is the union of all operations less the union of the
others.  ``None`` where the window holds no collective (one chip)."""
import re

_COLLECTIVE = re.compile(r"(?:collective-permute|all-reduce|all-gather|"
                         r"reduce-scatter|all-to-all)"
                         r"(?:-start|-update|-done)?(?:\.\d+)?")


def is_collective(op) -> bool:
    if op.opcode.startswith("async-"):
        return bool(_COLLECTIVE.fullmatch(op.hlo))
    return bool(_COLLECTIVE.fullmatch(op.opcode))


def read(ctx):
    union_s, exposed, found = ctx.trace.union_s, 0.0, False
    for ops in ctx.red.devices:
        coll = [is_collective(o) for o in ops]
        found = found or any(coll)
        exposed += (union_s([(o.start, o.end) for o in ops])
                    - union_s([(o.start, o.end)
                               for o, c in zip(ops, coll) if not c]))
    if not found:
        return None
    return 1e3 * exposed / len(ctx.red.devices) / ctx.epochs
