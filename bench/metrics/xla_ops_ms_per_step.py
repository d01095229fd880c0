"""Device milliseconds per lattice step of every op that is neither a
`tdp_windowed*` kernel nor a collective (pads, concatenates, the collide
and stream programs, the statistics' reductions), mean over the devices."""
from bench.lib import trace as tr


def _xla(name):
    return not tr.is_kernel(name) and not tr.is_collective(name)


def read(ctx):
    per_dev = [tr.op_seconds(ops, ctx.window, _xla) for ops in ctx.trace.devices.values()]
    if not per_dev or max(per_dev) == 0:
        return None
    return sum(per_dev) / len(per_dev) * 1e3 / ctx.steps
