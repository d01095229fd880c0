"""Host milliseconds of `sim.observables(state)` per call, from the
benchmark's own `bench.observables` span (until its floats are on the host)."""
from bench.lib import trace as tr


def read(ctx):
    spans = tr.clip(ctx.trace.span_intervals("bench.observables"), ctx.window)
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) / 1e6
