"""Process start to the window's opening: weights, warm-up, and the starting
population admitted where the mix has one."""


def read(ctx):
    return ctx.setup_s
