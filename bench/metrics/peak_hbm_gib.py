"""Peak device memory in use over the run (``peak_bytes_in_use``), on the
fullest chip, in GiB."""


def read(ctx):
    b = ctx.memory_peak_bytes
    return b / 2**30 if b else None
