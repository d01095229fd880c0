"""The whole step's share of the chip's peak, in %: the least time of the
steps completed in the traced window (the benchmark's own work per site
and peaks, bench/lib/work.py; HBM bandwidth binds), over the window."""
from bench.lib import work


def read(ctx):
    if ctx.steps == 0:
        return None
    least, _ = work.least_step_seconds(ctx.sites_per_chip, ctx.device_kind)
    return 100.0 * least * ctx.steps / ctx.window_s
