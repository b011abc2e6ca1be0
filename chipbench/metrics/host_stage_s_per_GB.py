"""host_stage_s_per_GB: seconds of host staging recorded by the program's
stage clock (``pack``: bytes to symbols, pack257, stripe transposes;
``pad``: bucket padding) during the window, per GB of the cell's work."""


def read(ctx):
    secs = ctx.stage_delta.get("pack", 0.0) + ctx.stage_delta.get("pad", 0.0)
    return ctx.per_GB(secs) if secs > 0 else None
