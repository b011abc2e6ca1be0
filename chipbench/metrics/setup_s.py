"""setup_s: seconds from the process's start to the window's start --
runtime up, data made, warm-up and compile-cache loads."""


def read(ctx):
    return ctx.setup_s
