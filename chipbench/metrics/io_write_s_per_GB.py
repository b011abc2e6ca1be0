"""io_write_s_per_GB: seconds the program's ``write`` stage recorded
during the window (each node file and manifest written and fsync'd by
the local blob backend, summed over the I/O pool's threads), per GB of
the cell's work."""


def read(ctx):
    secs = ctx.stage_delta.get("write", 0.0)
    return ctx.per_GB(secs) if secs > 0 else None
