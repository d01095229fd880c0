"""Share of the traced window, in %, in which no op runs on a device,
mean over the cell's devices."""
from bench.lib import trace as tr


def read(ctx):
    if not ctx.trace.devices:
        return None
    span = ctx.window[1] - ctx.window[0]
    busy = [tr.length(tr.op_intervals(ops, ctx.window)) for ops in ctx.trace.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / span)
