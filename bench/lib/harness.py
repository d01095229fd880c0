"""One run of one cell: set-up, the measured window, the per-layer reading
of a traced window, and the comparison that decides `correct`.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name (`bench/lib/registry.py`); nothing here names a
cell.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time
from dataclasses import dataclass

import jax
import numpy as np

from bench.lib import check, lattice, registry, trace as tr


@dataclass
class Window:
    """What the measured window did, and the one call kept for the check."""
    seconds: float
    calls: int
    steps: int
    obs: list
    kept_in: tuple
    kept_out: tuple
    kept_obs: dict


@dataclass
class Context:
    """What a per-layer metric's reader gets."""
    trace: tr.Trace
    window: tuple          # (start_ns, end_ns) of the `bench.window` span
    calls: int
    steps: int             # lattice steps completed in the window
    kernel_steps: int      # of those, steps the fused kernel made
    chips: int
    sites_per_chip: int
    device_kind: str

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9


def enable_compile_cache(root) -> None:
    """JAX's persistent compilation cache at a fixed place in the checkout,
    every program in it, no eviction."""
    import os

    jax.config.update("jax_compilation_cache_dir", os.path.join(os.fspath(root), ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def _span(on: bool):
    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def run_window(sim, state, traffic: dict, seconds: float, keep: int, spans: bool = False):
    """The closed loop of `traffic` for at least `seconds` (and through call
    `keep`): whole calls of `sim.run` + the host's statistics.  Returns the
    final state and the `Window`."""
    span = _span(spans)
    n, donate = int(traffic["steps_per_call"]), bool(traffic["donate"])
    obs = []
    kept_in = kept_out = kept_obs = None
    t0 = time.perf_counter()
    with span("bench.window"):
        k = 0
        while True:
            if k == keep:
                with span("bench.keep"):
                    kept_in = lattice.copy_fields(state.f, state.g)
            with span("bench.run"):
                state = sim.run(state, n, donate=donate)
            with span("bench.block"):
                jax.block_until_ready((state.f, state.g))
            with span("bench.observables"):
                o = sim.observables(state)
            obs.append(o)
            if k == keep:
                with span("bench.keep"):
                    kept_out = lattice.copy_fields(state.f, state.g)
                kept_obs = o
            k += 1
            if k > keep and time.perf_counter() - t0 >= seconds:
                break
    dt = time.perf_counter() - t0
    return state, Window(dt, k, k * n, obs, kept_in, kept_out, kept_obs)


def physics(cfg: dict) -> dict:
    """The configuration's physics as the reference takes it."""
    return {k: v for k, v in cfg["physics"].items() if k != "rho0"}


def reference_numbers(cfg: dict, traffic: dict, win: Window, ref) -> dict:
    """Run the plain reference from the kept call's input on the first
    device and compare the kept output with it."""
    dev = jax.devices()[0]
    f_in, g_in = (jax.device_put(x, dev) for x in win.kept_in)
    f_out, g_out = (jax.device_put(x, dev) for x in win.kept_out)
    f_ref, g_ref = ref.run(f_in, g_in, int(traffic["steps_per_call"]), physics(cfg))
    nums = check.field_numbers(f_out, g_out, f_ref, g_ref)
    nums["obs_gaps"] = check.obs_gaps(win.kept_obs, f_ref, g_ref, ref)
    nums["obs_gap"] = max(nums["obs_gaps"].values())
    return nums


def control_build(precision: str, build=None):
    """A builder of the system under test whose `run` is the plain
    reference at `precision`: the control of the comparison, which a run
    of the benchmark has to find not correct."""
    import copy

    from repro.lb.sim import LBState

    def make(cfg, devices):
        sim = copy.copy((build or lattice.build)(cfg, devices))
        ref, phys = registry.load_reference(cfg["reference"]), physics(cfg)

        def run(state, n, donate=False):
            return LBState(*ref.run(state.f, state.g, n, phys, precision), state.step + n)
        sim.run = run
        return sim
    return make


def peak_bytes(devices) -> int:
    """`peak_bytes_in_use` of the fullest device (0 where a backend keeps no
    such count, as the CPU's)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def sites_per_chip(cfg: dict) -> int:
    chips = int(np.prod(cfg["mesh"]["shape"])) if cfg["mesh"] else 1
    return int(np.prod(cfg["grid"])) // chips


def per_layer(root, bench: dict, cell: dict, ctx: Context) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = registry.load_metric(root, m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(ctx: Context) -> dict:
    """The ten device ops that took most time (seconds per chip), and the
    ten longest idle gaps, named by the benchmark span open on the host."""
    ops = {}
    for dev_ops in ctx.trace.devices.values():
        for name, s, d in dev_ops:
            if s < ctx.window[1] and s + d > ctx.window[0]:
                ops[name] = ops.get(name, 0.0) + d / 1e9 / len(ctx.trace.devices)
    idle = []
    for dev_ops in ctx.trace.devices.values():
        for s, e in tr.gaps(tr.op_intervals(dev_ops, ctx.window), ctx.window):
            idle.append([tr.innermost_span(ctx.trace.spans, (s + e) // 2), (e - s) / 1e9])
    return {"device_ops": sorted(([k, v] for k, v in ops.items()), key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(idle, key=lambda kv: -kv[1])[:10]}


def stamp(log, t_start: float, phase: str) -> None:
    """An information line: the set-up's seconds when `phase` ended."""
    log(f"[bench] info setup {phase} done at {time.perf_counter() - t_start:.3f} s")


def run_cell(root, name: str, seed: int, seconds: float, trace: bool, t_start: float,
             log=print, build=None) -> dict:
    """One run of cell `name`; returns the result object.  The caller has
    checked the platform and the chip count.  `build(cfg, devices)` makes
    the system under test (`lattice.build` unless given)."""
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, name)
    cfg = registry.load_config(root, cell["config"])
    traffic = registry.load_traffic(root, cell["traffic"])
    ref = registry.load_reference(cfg["reference"])
    devices = jax.devices()[:int(cell["chips"])]
    kind = devices[0].device_kind

    sim = (build or lattice.build)(cfg, devices)
    stamp(log, t_start, "build")
    for line in lattice.info_lines(cfg, sim):
        log(line)
    state = lattice.init_state(cfg, sim, seed, ref)
    stamp(log, t_start, "init_state")
    keep = check.sample_call(seed)
    # Warm-up: every program the window runs, at the window's shapes.
    state, _ = run_window(sim, state, traffic, 0.0, keep=0)
    stamp(log, t_start, "warm-up")
    log(f"[bench] info peak_bytes_in_use_after_warmup={peak_bytes(devices)}")
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        state, win = run_window(sim, state, traffic, seconds, keep, spans=trace)
        if trace:
            jax.profiler.stop_trace()
        memory_peak = peak_bytes(devices)
        placement = {d.id for d in win.kept_out[0].sharding.device_set}
        del state, sim
        gc.collect()

        result = {"correct": False, "attempted": win.calls,
                  "failed": sum(bool(o["nan"]) for o in win.obs)}
        if trace:
            t = tr.from_xplane(tr.find_xplane(trace_dir), {d.id for d in devices})
            ctx = Context(t, t.window(), win.calls, win.steps,
                          lattice.kernel_steps(cfg, win.calls, int(traffic["steps_per_call"])),
                          len(devices), sites_per_chip(cfg), kind)
            result["metrics"] = per_layer(root, bench, cell, ctx)
            busy = np.mean([tr.length(tr.op_intervals(ops, ctx.window))
                            for ops in t.devices.values()]) / 1e9
            device_extra = {"busy_s": float(busy), "window_s": ctx.window_s}
        else:
            rate = win.steps * np.prod(cfg["grid"]) / win.seconds / len(devices) / 1e6
            units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
            result["metrics"] = {"mlups_per_chip": {"value": float(rate),
                                                    "unit": units["mlups_per_chip"]},
                                 "setup_s": {"value": setup_s, "unit": units["setup_s"]}}
            device_extra = {}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    result["device"] = {"platform": devices[0].platform, "kind": kind,
                        "count": len(jax.devices()), "memory_peak_bytes": memory_peak,
                        **device_extra}
    if trace:
        result["breakdown"] = breakdown(ctx)

    numbers = reference_numbers(cfg, traffic, win, ref)
    numbers.update(check.window_numbers(win.obs))
    if cfg["mesh"] is not None:
        numbers["devices_missing"] = len({d.id for d in devices} - placement)
    correct, compared = check.verdict(numbers, cfg["limits"])
    result["correct"] = correct
    result["check"] = compared
    return result
