"""Set-up seconds: process start to the window's opening (imports, TPU
start-up, zone generation and appends, warm-up and any compile)."""


def read(ctx):
    return ctx.setup_s
