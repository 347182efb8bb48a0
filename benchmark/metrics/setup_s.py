"""Process start to the window's opening: boot, load, warm-up, compile."""


def read(ctx):
    return ctx.setup_s
