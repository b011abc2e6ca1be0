"""gf_roofline_pct: the share of the HBM roofline that the GF executables
reach on the window's work.  The least time the cell's chips could take
is the bytes the work needs (``work.py``: unpadded symbols read and
written, one byte each) over the peak HBM bandwidth of all of them; the
time taken is the device time of the plan cache's executables in the
trace, averaged over the same chips.  The bytes bound it: a GF symbol
costs at most 2k multiply-adds, far below the int8 peak."""


def read(ctx):
    if ctx.trace is None or ctx.trace["gf_device_s"] <= 0 or not ctx.gf_bytes:
        return None
    least_s = ctx.gf_bytes / (ctx.peaks["hbm_bytes_per_s"]
                              * ctx.trace["devices"])
    return 100.0 * least_s / ctx.trace["gf_device_s"]
