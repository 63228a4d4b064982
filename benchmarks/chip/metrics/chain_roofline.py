"""Share of the HBM roofline reached by the sort's device programs: the
least time the chip could take to read every key once and write every
sorted key or row id once, at their stored width, over the device time
of the programs the traffic names (``chain_programs``, matched by HLO
module name) in the traced window.  Nothing where no such program ran.
"""


def read(ctx):
    if ctx.trace is None:
        return None
    device_s = ctx.trace.module_seconds(ctx.traffic.get("chain_programs", ()))
    if device_s <= 0:
        return None
    least_s = (ctx.window.attempted * ctx.cell.rows_per_call * ctx.cell.min_bytes_per_row
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / device_s
