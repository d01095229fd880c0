"""Helpers shared by the benchmark's tests."""
import copy
import json
import os
import shutil
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import harness, trace as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000   # ns


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


#: `obs_gap`'s limit at the CPU's sizes: the CPU sums the statistics in
#: float32 one element after another, which alone reads up to ~1e-6 at 16³
#: (2.3e-6 with the reference's own fields in the program's place), above
#: what the chip's tree reductions read; every other limit is the cell's.
CPU_OBS_GAP = 1e-5


def small_config(name, base="ludwig_spinodal_128", **changes):
    """A configuration file's content at a size the CPU holds: the base
    configuration with `changes`."""
    cfg = copy.deepcopy(load_json("bench", "configs", f"{base}.json"))
    cfg.update(name=name, **changes)
    cfg["limits"]["obs_gap"] = CPU_OBS_GAP
    return cfg


class ScratchRoot:
    """A checkout root holding BENCHMARK.json and the benchmark's files, to
    which `add(kind, name, content)` adds a file and `add_cell` a cell."""

    def __init__(self, root):
        self.root = root
        self.path = str(root)
        shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        self.bench = load_json("BENCHMARK.json")
        self.write()

    def add(self, kind, name, content):
        ext = "py" if kind == "metrics" else "json"
        p = self.root / "bench" / kind / f"{name}.{ext}"
        assert not p.exists(), f"{p} exists: a new file must not replace one"
        p.write_text(content if isinstance(content, str) else json.dumps(content))

    def add_cell(self, cell):
        self.bench["workloads"].append(cell)
        self.write()

    def add_metric(self, metric):
        self.bench["per_layer"].append(metric)
        self.write()

    def write(self):
        (self.root / "BENCHMARK.json").write_text(json.dumps(self.bench))


def ctx_of(trace, steps=10, kernel_steps=9, chips=1, calls=1, sites=128 ** 3):
    return harness.Context(trace, trace.window(), calls, steps, kernel_steps, chips, sites,
                           "TPU v5 lite")


def hand_trace():
    """Two devices over a 100 ms window: each runs a kernel, an XLA op and a
    collective-permute, which on device 1 is in flight over its ops."""
    t = tr.Trace()
    t.spans = [("bench.window", 0, 100 * MS), ("bench.run", 0, 1 * MS),
               ("bench.block", 1 * MS, 89 * MS), ("bench.observables", 90 * MS, 10 * MS)]
    t.devices = {
        "/device:TPU:0": [("tdp_windowed_fused_p1_soa", 0, 50 * MS),
                          ("fusion.1", 50 * MS, 10 * MS),
                          ("collective-permute-done.3", 60 * MS, 20 * MS)],
        "/device:TPU:1": [("tdp_windowed_fused_p1_soa", 0, 40 * MS),
                          ("pad.2", 50 * MS, 20 * MS)],
    }
    # device 1's exchange is asynchronous: in flight 30-60 ms, over its ops
    t.async_ops = {"/device:TPU:1": [("collective-permute-start.3", 30 * MS, 30 * MS),
                                     ("copy-start.1", 0, 5 * MS)]}
    return t


def recorded(name):
    """The short traced window recorded on the chip for cell `name`."""
    with open(os.path.join(DATA, f"{name}.short.ctx.json")) as fh:
        c = json.load(fh)
    t = tr.Trace.from_json(os.path.join(DATA, f"{name}.short.trace.json.gz"))
    return harness.Context(t, t.window(), c["calls"], c["steps"], c["kernel_steps"],
                           c["chips"], c["sites_per_chip"], c["device_kind"])
