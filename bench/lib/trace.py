"""From a profiler trace to the events the per-layer metrics read.

A `Trace` keeps, for each device, the operations that ran on it (name,
start, duration, in nanoseconds on the host's clock) and the host spans
the benchmark opened (`bench.window`, `bench.run`, `bench.block`,
`bench.observables`).  The interval arithmetic below is what every
per-layer metric is made of, so a metric reads the same number whoever
changes the program.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from dataclasses import dataclass, field

#: The device line of a TPU plane that holds one event per operation, and
#: the one that holds each asynchronous op's time in flight.
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
#: Prefix of the host spans the benchmark writes (`TraceAnnotation`).
SPAN_PREFIX = "bench."
#: Prefix of the windowed Pallas kernels' names.
KERNEL_PREFIX = "tdp_windowed"
#: Substrings that mark a collective operation in an op's name.
COLLECTIVE_MARKS = ("collective-permute", "all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-broadcast")


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # device -> [(op, start_ns, dur_ns)], leaf ops
    spans: list = field(default_factory=list)     # [(span, start_ns, dur_ns)]
    async_ops: dict = field(default_factory=dict)  # device -> [(op, start_ns, dur_ns)] in flight

    def to_json(self, path: str) -> None:
        with gzip.open(path, "wt") as fh:
            json.dump({"devices": self.devices, "spans": self.spans,
                       "async_ops": self.async_ops}, fh)

    @classmethod
    def from_json(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as fh:
            d = json.load(fh)
        return cls({k: [tuple(e) for e in v] for k, v in d["devices"].items()},
                   [tuple(s) for s in d["spans"]],
                   {k: [tuple(e) for e in v] for k, v in d.get("async_ops", {}).items()})

    def span_intervals(self, name: str) -> list:
        return [(s, s + d) for n, s, d in self.spans if n == name]

    def window(self) -> tuple[int, int]:
        """The measured window: the one `bench.window` span."""
        (w,) = self.span_intervals(SPAN_PREFIX + "window")
        return w


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return paths[0]


def op_name(text: str) -> str:
    """An op's HLO name from the trace's event text
    (`%pad_maximum_fusion.21 = f32[...] fusion(...)` -> `pad_maximum_fusion.21`)."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def leaves(events) -> list:
    """The events that contain no other event: a `while` (the scanned steps)
    is a parent of its body's ops on the same line, and would count twice."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (n, s, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < s + d and nxt[1] + nxt[2] <= s + d:
            continue                                    # it holds the next event
        out.append((n, s, d))
    return out


def from_xplane(path: str, device_ids=None) -> Trace:
    """The device ops and the benchmark's host spans of one xplane file.
    `device_ids` keeps only the planes `/device:TPU:<id>` of those ids."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            tail = name[len("/device:TPU:"):]
            if not tail.isdigit():
                continue          # e.g. a SparseCore plane
            if device_ids is not None and int(tail) not in device_ids:
                continue
            for line in plane.lines:
                evs = [(op_name(e.name), int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
                if line.name == OPS_LINE:
                    tr.devices[name] = leaves(evs)
                elif line.name == ASYNC_LINE:
                    tr.async_ops[name] = sorted(evs, key=lambda e: e[1])
        elif name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                for e in line.events if e.name.startswith(SPAN_PREFIX))
    tr.spans.sort(key=lambda s: s[1])
    return tr


# -- interval arithmetic -------------------------------------------------------

def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def clip(intervals, window) -> list:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def exposed(target, others) -> int:
    """Length of the union of `target` during which no `others` interval runs."""
    t, o = union(target), union(others)
    covered, j = 0, 0
    for s, e in t:
        while j < len(o) and o[j][1] <= s:
            j += 1
        k = j
        while k < len(o) and o[k][0] < e:
            covered += min(e, o[k][1]) - max(s, o[k][0])
            k += 1
    return sum(e - s for s, e in t) - covered


def gaps(intervals, window) -> list:
    """The idle (start, end) stretches of `window` outside `intervals`."""
    lo, hi = window
    out, cur = [], lo
    for s, e in union(clip(intervals, window)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def innermost_span(spans, t) -> str:
    """Name of the innermost benchmark span open at time `t` ("none" if none)."""
    best, best_len = "none", None
    for n, s, d in spans:
        if s <= t < s + d and n != SPAN_PREFIX + "window" and (best_len is None or d < best_len):
            best, best_len = n, d
    return best


def is_kernel(op: str) -> bool:
    return op.startswith(KERNEL_PREFIX)


def is_collective(op: str) -> bool:
    return any(m in op for m in COLLECTIVE_MARKS)


def is_exchange(op: str) -> bool:
    return "collective-permute" in op


def op_intervals(ops, window, pick=lambda op: True) -> list:
    return clip([(s, s + d) for n, s, d in ops if pick(n)], window)


def op_seconds(ops, window, pick) -> float:
    """Summed device time (not the union) of the picked ops in `window`."""
    return sum(e - s for s, e in op_intervals(ops, window, pick)) / 1e9


def exchange_intervals(trace: Trace, device: str, window) -> list:
    """When a collective-permute runs on `device` as an op, or is in flight
    as an asynchronous one, within `window`."""
    evs = trace.devices.get(device, []) + trace.async_ops.get(device, [])
    return union(op_intervals(evs, window, is_exchange))
