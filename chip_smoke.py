#!/usr/bin/env python3
"""Smoke run of the fused D3Q19 binary-fluid step on a TPU.

Drives the LB main path through its normal entry point,
``repro.lb.sim.BinaryFluidSim.run(..., donate=True)``, at the upstream
per-device size (Ludwig's ~128³ per device, ``configs/ludwig_lb.py``)
from the spinodal initialisation of ``examples/lb_spinodal.py``, and
checks every phase against the unfused ``xla`` Program at the same grid
and step count:

* default (one chip, 128³, 20 steps):
  (a) ``pallas_windowed`` fused ``two_launch``;
  (b) ``pallas_windowed`` fused ``one_launch``;
  (c) the reference, unfused ``xla``.
* ``--four-chips`` (only this phase): the 2×2 pencil over
  ``jax.devices()[:4]`` at a 256×256×128 global grid, fused
  ``two_launch`` under ``xla`` and under ``pallas_windowed``, against the
  unfused ``xla`` Program on one device at the same global grid.

A phase passes when its fields hold no NaN, its relative mass drift is
at most ``MASS_DRIFT_TOL`` and its largest absolute difference from the
reference in ``f`` and in ``g`` is at most ``ATOL``.  Each phase prints
one line; the last line is one JSON object naming the device.  The run
exits non-zero, and prints no result, when JAX finds no TPU or when any
phase fails.  There is no CPU path and no interpret mode.

Run:  python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import tdp  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.lb.params import LBParams  # noqa: E402
from repro.lb.sim import BinaryFluidSim  # noqa: E402

STEPS = 20
SEED = 0
NOISE = 0.05
PARAMS = LBParams(A=0.125, B=0.125, kappa=0.02)   # examples/lb_spinodal.py

#: Largest absolute difference allowed from the reference in f and g.
#: The phases run the same float32 physics in another order (fused vs
#: unfused launches, Mosaic vs XLA code, sharded vs one device), so they
#: agree to rounding, not bitwise.  f and g are O(1) (f_i ≈ w_i·ρ ≤ 1/3,
#: |g_i| ≤ 0.05/3): one step rounds at ~6e-8 of that, and 20 steps grow
#: it to ~1e-6 at most.  1e-5 leaves a tenfold margin, while a wrong
#: neighbour, weight or ghost plane moves values by the noise amplitude
#: itself (~1e-3 and up).
ATOL = 1e-5
#: Largest relative change of the total mass Σf over the run: the
#: collision conserves mass exactly, so only float32 rounding moves it.
MASS_DRIFT_TOL = 1e-5


def run_phase(grid, executor, fused, *, mesh=None, shard_axis="data"):
    """One phase: build the sim, run ``STEPS`` donated steps twice (the
    first call compiles), return the host fields and the timings."""
    sim = BinaryFluidSim(grid, params=PARAMS, target=tdp.Target(executor),
                         fused=fused, mesh=mesh, shard_axis=shard_axis)
    hot = sim.programs["fused" if fused else "step"]
    comm = hot.comm_stats() if mesh is not None else None
    times = []
    for _ in range(2):
        state = sim.init_spinodal(seed=SEED, noise=NOISE)
        jax.block_until_ready((state.f, state.g))
        t0 = time.perf_counter()
        state = sim.run(state, STEPS, donate=True)
        jax.block_until_ready((state.f, state.g))
        times.append(time.perf_counter() - t0)
    placement = sorted(d.id for d in state.f.sharding.device_set)
    shard_shapes = sorted({tuple(s.data.shape)
                           for s in state.f.addressable_shards})
    # f, g to the host, then free the phase's device buffers
    f, g = np.asarray(state.f), np.asarray(state.g)
    del state, sim, hot
    gc.collect()
    first, steady = times
    return {"f": f, "g": g, "compile_s": first - steady, "steady_s": steady,
            "comm": comm, "placement": placement,
            "shard_shapes": shard_shapes}


def mass_drift(f0_mass, f):
    return abs(float(f.sum(dtype=np.float64)) - f0_mass) / f0_mass


def initial_mass(grid):
    """Σf of the spinodal start: f_i = w_i·ρ0 at every site."""
    return float(PARAMS.rho0) * float(np.prod(grid))


def check(name, res, ref, grid):
    """The phase's report line and its list of failures."""
    f, g = res["f"], res["g"]
    fails = []
    nan = bool(np.isnan(f).any() or np.isnan(g).any())
    if nan:
        fails.append("NaN in fields")
    drift = mass_drift(initial_mass(grid), f)
    if not drift <= MASS_DRIFT_TOL:
        fails.append(f"mass drift {drift:.3e} > {MASS_DRIFT_TOL:.0e}")
    df = float(np.abs(f - ref["f"]).max())
    dg = float(np.abs(g - ref["g"]).max())
    if not (df <= ATOL and dg <= ATOL):
        fails.append(f"max|Δf|={df:.3e} max|Δg|={dg:.3e} > atol {ATOL:.0e}")
    nsites = int(np.prod(grid))
    line = (f"[chip_smoke] {name}: grid={'x'.join(map(str, grid))} "
            f"steps={STEPS} compile_s={res['compile_s']:.2f} "
            f"steady_msites_s={nsites * STEPS / res['steady_s'] / 1e6:.1f} "
            f"mass_drift={drift:.3e} max_abs_df={df:.3e} "
            f"max_abs_dg={dg:.3e} nan={nan} devices={res['placement']} "
            f"shard_shapes={res['shard_shapes']} "
            f"{'PASS' if not fails else 'FAIL: ' + '; '.join(fails)}")
    return line, fails


def _run_checked(name, ref, grid, executor, fused, **kw):
    """Run one phase against the reference; returns its failures."""
    try:
        res = run_phase(grid, executor, fused, **kw)
    except Exception as e:  # noqa: BLE001 — reported, then exit 1
        traceback.print_exc()
        print(f"[chip_smoke] {name}: FAIL: {type(e).__name__}: {e}",
              flush=True)
        return [f"{type(e).__name__}"]
    if res["comm"] is not None:
        cs = res["comm"]
        print(f"[chip_smoke] {name} comm_stats: "
              f"decomposition={cs['decomposition']} "
              f"local_shape={cs['local_shape']} "
              f"exchange_schedule={cs['exchange_schedule']} "
              f"ppermutes_per_step={cs['ppermutes_per_step']} "
              f"exchanged_bytes_per_step={cs['exchanged_bytes_per_step']}",
              flush=True)
    line, fails = check(name, res, ref, grid)
    mesh = kw.get("mesh")
    want = (sorted(d.id for d in mesh.devices.flat) if mesh is not None
            else [jax.devices()[0].id])
    if res["placement"] != want:
        fails.append(f"state on devices {res['placement']}, not {want}")
        line += f"; FAIL: state on devices {res['placement']}"
    print(line, flush=True)
    return fails


def _reference(name, grid):
    ref = run_phase(grid, "xla", False)
    line, fails = check(name, ref, ref, grid)
    print(line, flush=True)
    return ref, fails


def one_chip():
    grid = (128, 128, 128)
    name = "(c) xla unfused [reference]"
    ref, fails = _reference(name, grid)
    failed = [name] if fails else []
    for name, fused in (("(a) pallas_windowed two_launch", "two_launch"),
                        ("(b) pallas_windowed one_launch", "one_launch")):
        if _run_checked(name, ref, grid, "pallas_windowed", fused):
            failed.append(name)
        gc.collect()
    return failed


def four_chips():
    devices = jax.devices()[:4]
    if len(devices) < 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                         f"{len(devices)}")
    grid = (256, 256, 128)
    mesh = Mesh(np.array(devices).reshape(2, 2), ("px", "py"))
    name = "xla unfused, one device [reference]"
    ref, fails = _reference(name, grid)
    failed = [name] if fails else []
    for executor in ("xla", "pallas_windowed"):
        name = f"2x2 pencil {executor} two_launch"
        if _run_checked(name, ref, grid, executor, "two_launch", mesh=mesh,
                        shard_axis=("px", "py")):
            failed.append(name)
        gc.collect()
    return failed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 pencil phase on four chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX's first device is "
              f"{dev.platform!r}; this smoke run has no CPU path",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    failed = four_chips() if args.four_chips else one_chip()
    if failed:
        print(f"[chip_smoke] failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
