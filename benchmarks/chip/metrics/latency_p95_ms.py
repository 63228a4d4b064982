"""95th percentile, by nearest rank, of every call in the window."""

import harness


def read(ctx):
    return 1e3 * harness.nearest_rank(ctx.window.latencies, 0.95)
