"""ckpt_restore_MBps: state bytes of every restore begun in the window,
over the time from the window's start to the end of the last of them."""


def read(ctx):
    return ctx.rate_MBps("restore")
