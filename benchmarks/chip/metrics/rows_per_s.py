"""Rows (keys, or table rows the query reads) over the whole window,
the check's fetches left out."""


def read(ctx):
    return ctx.window.rows / ctx.window.elapsed
