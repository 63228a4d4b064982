"""Seconds from the start of the process to the start of the window:
start-up, data, compile or cache load, and the warm call."""


def read(ctx):
    return ctx.setup_s
