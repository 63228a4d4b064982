"""Bytes the query's operators bring from the device to the host
(``query.d2h_bytes``, as the cell recorded it for each call of the
window) per row the calls read.  Nothing where the program counts no
such bytes."""


def read(ctx):
    calls = getattr(ctx.cell, "counters", [])[-ctx.window.attempted:]
    moved = sum(c.get("query.d2h_bytes", 0) for c in calls)
    if moved <= 0:
        return None
    return moved / (ctx.window.attempted * ctx.cell.rows_per_call)
