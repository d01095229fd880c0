"""Device milliseconds per lattice step in which a halo exchange
(a collective-permute, as an op or in flight) is under way, on the device
where that is longest."""
from bench.lib import trace as tr


def read(ctx):
    per_dev = [tr.length(tr.exchange_intervals(ctx.trace, dev, ctx.window))
               for dev in ctx.trace.devices]
    if not per_dev or max(per_dev) == 0:
        return None
    return max(per_dev) / 1e6 / ctx.steps
