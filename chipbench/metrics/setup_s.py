"""Seconds from the start of the process to the opening of the window."""


def read(ctx):
    return ctx.setup_s
