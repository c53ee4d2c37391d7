"""Halo bytes on the wire per epoch, the plan's exact count
(``StepCounters.wire_bytes``), in MB (1e6 bytes)."""


def read(ctx):
    return ctx.wire_bytes / ctx.epochs / 1e6 if ctx.wire_bytes else None
