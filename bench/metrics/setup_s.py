"""Set-up seconds: from the start of the process to the end of warm-up."""


def read(ctx):
    return ctx.setup_s
