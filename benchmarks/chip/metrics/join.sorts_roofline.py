"""Share of the HBM roofline reached by the query's sorts: the least
time the chip could take to move what the window's pass chains must move
(``query.sort_min_bytes``: each chain's prepared key columns read once
and an int32 row id written once a row, as the cell recorded it for
each call), over the device time of the programs the traffic names
(``chain_programs``) in the traced window.  Nothing where the program
counts no such bytes or no such program ran."""


def read(ctx):
    if ctx.trace is None:
        return None
    calls = getattr(ctx.cell, "counters", [])[-ctx.window.attempted:]
    least_bytes = sum(c.get("query.sort_min_bytes", 0) for c in calls)
    device_s = ctx.trace.module_seconds(ctx.traffic.get("chain_programs", ()))
    if least_bytes <= 0 or device_s <= 0:
        return None
    return 100.0 * least_bytes / ctx.peaks["hbm_bytes_per_s"] / device_s
