"""Milliseconds per lattice step in which a halo exchange is under way on
a device and no other op runs there, on the device where that is longest."""
from bench.lib import trace as tr


def read(ctx):
    per_dev = []
    for dev, ops in ctx.trace.devices.items():
        ex = tr.exchange_intervals(ctx.trace, dev, ctx.window)
        if ex:
            others = tr.op_intervals(ops, ctx.window, lambda n: not tr.is_exchange(n))
            per_dev.append(tr.exposed(ex, others))
    if not per_dev:
        return None
    return max(per_dev) / 1e6 / ctx.steps
