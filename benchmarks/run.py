"""Benchmark harness — one benchmark per paper table/figure.

Paper artefacts reproduced:

* **Fig. 1** (`bench_fig1`): Ludwig binary-collision runtime, *original*
  (AoS, model-dictated innermost extents 19/3) vs *targetDP* (SoA,
  VVL-chunked sites) — on the CPU host, plus the Pallas-interpret backend
  to demonstrate the single-source portability contract.
* **VVL tuning curve** (`bench_vvl`): the paper's central claim — a
  *tunable* ILP extent exposes performance the compiler cannot find from
  model-dictated loops.  We sweep VVL exactly as §IV tunes 8 (CPU) / 2
  (GPU).
* **Masked transfers** (`bench_masked_copy`): §III-B's compressed copies
  vs full-lattice copies at several subset densities.
* **Fused stream+collide** (`bench_fused_step`): the follow-up paper's
  (1609.01479) fusion claim — one stencil launch per LB timestep
  (stream → ∇φ → collide, no intermediate full-lattice arrays) vs the
  unfused moment/stencil/collide/stream pipeline, per-site wall cost;
  plus the `tdp.Program` variant (the whole step as a compiled graph,
  scanned under one `lax.scan` with donated ping-pong buffers).
* **Streaming / gradient launches** (`bench_stream`, `bench_grad`): the
  two building-block stencil launches across executors — the per-launch
  records the fused numbers decompose into.
* **LM token throughput** (`bench_lm_step`): the token-lattice pointwise
  family (rmsnorm / gated-act) through the same tdp backends — the
  framework-integration claim (DESIGN.md §4).

Wall-times here are CPU numbers (this container); they demonstrate the
*tuning structure* (relative effects), while the TPU roofline lives in
benchmarks/roofline.py (static analysis of the dry-run artifacts).

Usage: ``PYTHONPATH=src python -m benchmarks.run [--quick]
[--only a,b,...] [--json] [--sweep plane_block=1,2,4]``

``--json`` additionally writes one machine-readable
``BENCH_<name>.json`` per benchmark that ran (median/min wall times,
grid size, executor per variant) under ``--out`` — the cross-PR perf
trajectory; the nightly CI lane uploads them as artifacts.

``--sweep key=v1,v2,...`` re-runs the windowed-executor variants of the
stencil benches once per value of any ``Target.tuning`` knob the
executor *declares* (``tdp.executor_tunables``; e.g. ``plane_block``)
and records the per-value medians into the bench JSON under
``"sweep"``.  A knob the executor ignores exits 2 up front — a silently
ignored sweep would read as "ran".

``--autotune`` closes the tuning loop: ``tdp.autotune`` runs over
``bench_fused_step``'s fused Program (windowed target), the chosen
tuning + full ``TuneReport`` land in ``BENCH_fused_step.json`` under
the ``"tuning"`` / ``"autotune"`` keys (extending, not replacing, the
PR 3/4 record schema), and the measured choice persists in the
``results/tuning/`` cache — a re-run reproduces it without measuring.
``--grid N`` / ``--steps K`` shrink the lattice / timing repetitions
for smoke runs (the CI fast lane runs ``--autotune --grid 8 --steps
2``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache

RESULTS = {}

#: per-bench machine-readable records (written by --json): name →
#: {"grid": ..., "variants": {label: {"median_s", "min_s", "executor"}}}
BENCH_RECORDS = {}

#: ``--sweep key=v1,v2,...`` values (parsed by main); benches with a
#: windowed-executor variant consult this and record one extra variant
#: per value under the bench record's "sweep" key.
SWEEPS: dict[str, list] = {}

#: the executor the sweep consumers retune — sweep keys are validated
#: against its declared tunables (``tdp.executor_tunables``) in main().
SWEEP_EXECUTOR = "pallas_windowed"

#: --windowed NAME: the spelling the windowed variants run under —
#: ``pallas_windowed_interpret`` (the Pallas interpreter, any host) or
#: ``pallas_windowed`` (compiled by Mosaic; TPU only).
WINDOWED = "pallas_windowed_interpret"

#: display/record abbreviations for sweep-variant keys (keeps the
#: PR 4 ``fused_windowed_pb<N>`` JSON spelling stable).
_KNOB_ABBREV = {"plane_block": "pb"}

#: --grid N / --steps K overrides (None → bench defaults).
GRID_OVERRIDE: int | None = None
REPS_OVERRIDE: int | None = None

#: --autotune: run tdp.autotune over bench_fused_step's Program and
#: record the choice + report into its BENCH JSON.
AUTOTUNE = False
TUNING_CACHE = "results/tuning"

#: --top-k K: predictor-guided autotune — rank the candidate space by
#: the cost-model's predicted time and measure only the base target plus
#: the K best-predicted candidates (None → measure everything).
TOP_K: int | None = None

#: --predict: annotate each fused_step variant with the cost model's
#: predicted step time (predicted_s / predicted_vs_measured /
#: bottleneck) so the bench JSON tracks model fidelity over time.
PREDICT = False


def _grid(default: tuple) -> tuple:
    if GRID_OVERRIDE is not None:
        return (GRID_OVERRIDE,) * len(default)
    return default


def _sweep_variants(base_target):
    """``(knob, value, record_suffix, display_suffix, target)`` per swept
    knob value — the generic spelling of the old plane_block-only loop."""
    out = []
    for key, vals in SWEEPS.items():
        short = _KNOB_ABBREV.get(key, f"{key}_")
        for v in vals:
            out.append((key, v, f"{short}{v}", f"{key}={v}",
                        base_target.with_tuning({key: v})))
    return out


def _time_stats(fn, *args, reps=5, warmup=2):
    """{"median_s", "min_s"} over ``reps`` timed calls."""
    if REPS_OVERRIDE is not None:
        reps, warmup = REPS_OVERRIDE, min(warmup, 1)
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return {"median_s": float(np.median(ts)), "min_s": float(np.min(ts))}


def _time(fn, *args, reps=5, warmup=2):
    return _time_stats(fn, *args, reps=reps, warmup=warmup)["median_s"]


def _table(title, rows, headers):
    out = [f"\n### {title}\n", "| " + " | ".join(headers) + " |",
           "|" + "---|" * len(headers)]
    for r in rows:
        out.append("| " + " | ".join(str(c) for c in r) + " |")
    text = "\n".join(out)
    print(text, flush=True)
    return text


# ---------------------------------------------------------------------------
# Fig. 1 — original vs targetDP, CPU + pallas-interpret
# ---------------------------------------------------------------------------

def bench_fig1(quick=False):
    from repro.lb import baseline, stencil
    from repro.lb.params import LBParams
    from repro.kernels import ops
    from repro.kernels.lb_collision import NVEL

    grid = (24, 24, 24) if quick else (32, 32, 32)
    n = int(np.prod(grid))
    p = LBParams()
    rng = np.random.default_rng(0)
    f = jnp.asarray(0.05 * rng.normal(size=(NVEL, n)) + 1 / 19., jnp.float32)
    g = jnp.asarray(0.05 * rng.normal(size=(NVEL, n)), jnp.float32)
    phi = g.sum(0, keepdims=True)
    gp = jnp.asarray(0.01 * rng.normal(size=(3, n)), jnp.float32)
    d2 = jnp.asarray(0.01 * rng.normal(size=(1, n)), jnp.float32)

    # original: AoS layout, innermost extents 19/3
    f_aos, g_aos = f.T, g.T
    gp_aos = gp.T

    t_orig = _time(jax.jit(
        lambda *a: baseline.collide_aos(*a, p)), f_aos, g_aos, phi[0],
        gp_aos, d2[0])

    from repro import tdp

    best = {}
    for backend in ("xla", "pallas_interpret"):
        vvls = (64, 128) if quick else (32, 64, 128, 256, 512)
        times = {}
        for vvl in vvls:
            tgt = tdp.Target(backend, vvl=vvl)
            fn = jax.jit(lambda *a, t=tgt: ops.lb_collision(
                *a, target=t, **p.as_kwargs()))
            times[vvl] = _time(fn, f, g, phi, gp, d2)
        best[backend] = min(times.items(), key=lambda kv: kv[1])
        RESULTS[f"fig1_vvl_{backend}"] = times

    msites = n / 1e6
    rows = [("original (AoS, extents 19/3)", "-",
             f"{t_orig*1e3:.2f}", f"{msites/t_orig:.1f}", "1.00×")]
    for backend, (vvl, t) in best.items():
        rows.append((f"targetDP [{backend}]", vvl, f"{t*1e3:.2f}",
                     f"{msites/t:.1f}", f"{t_orig/t:.2f}×"))
    RESULTS["fig1"] = {"grid": grid, "t_original_s": t_orig,
                       "best": {k: {"vvl": v[0], "t_s": v[1]}
                                for k, v in best.items()}}
    BENCH_RECORDS["fig1"] = {
        "grid": list(grid),
        "variants": {"original_aos": {"median_s": t_orig, "executor": "xla"},
                     **{f"targetdp_{k}": {"median_s": v[1], "executor": k,
                                          "vvl": v[0]}
                        for k, v in best.items()}}}
    return _table(
        f"Fig. 1 — binary collision, {grid} lattice ({n} sites)",
        rows, ["implementation", "VVL", "ms/step", "Msites/s", "speedup"])


# ---------------------------------------------------------------------------
# VVL tuning curve
# ---------------------------------------------------------------------------

def bench_vvl(quick=False):
    times = RESULTS.get("fig1_vvl_xla")
    if times is None:
        bench_fig1(quick)
        times = RESULTS["fig1_vvl_xla"]
    tmin = min(times.values())
    rows = [(v, f"{t*1e3:.2f}", f"{t/tmin:.2f}×")
            for v, t in sorted(times.items())]
    RESULTS["vvl_curve"] = {str(k): v for k, v in times.items()}
    BENCH_RECORDS["vvl"] = {
        "variants": {f"vvl{v}": {"median_s": t, "executor": "xla", "vvl": v}
                     for v, t in sorted(times.items())}}
    return _table("VVL tuning curve (xla backend, paper §IV methodology)",
                  rows, ["VVL", "ms/step", "vs best"])


# ---------------------------------------------------------------------------
# masked vs full copies (paper §III-B)
# ---------------------------------------------------------------------------

def bench_masked_copy(quick=False):
    from repro.core import (Field, Lattice, copy_from_target,
                            copy_from_target_masked, copy_to_target)

    side = 48 if quick else 64
    lat = Lattice((side, side, side))
    f = Field(lat, ncomp=19, dtype=np.float32)
    rng = np.random.default_rng(1)
    f.data[...] = rng.normal(size=f.array_shape).astype(np.float32)
    t = copy_to_target(f)
    jax.block_until_ready(t)

    # On-host wall time cannot show the paper's win (device_get of a local
    # CPU array is a memcpy); the §III-B claim is about *link* traffic
    # (PCIe then, ICI/DCN now).  Report wire bytes + modelled link time at
    # 16 GB/s alongside the measured pack cost.
    LINK = 16e9
    t_full = _time(lambda: np.asarray(jax.device_get(t)), reps=3)
    full_bytes = f.data.nbytes
    rows = [("full lattice", "100%", f"{full_bytes/2**20:.1f}",
             f"{full_bytes/LINK*1e3:.2f}", f"{t_full*1e3:.2f}", "1.00×")]
    for frac in (0.01, 0.1, 0.5):
        mask = rng.random(lat.nsites) < frac
        host = Field(lat, 19, np.float32)
        tm = _time(lambda m=mask, h=host: copy_from_target_masked(t, m, h),
                   reps=3)
        wire = int(mask.sum()) * 19 * 4
        rows.append(("masked subset", f"{frac:.0%}", f"{wire/2**20:.1f}",
                     f"{wire/LINK*1e3:.2f}", f"{tm*1e3:.2f}",
                     f"{full_bytes/wire:.1f}×"))
    RESULTS["masked_copy"] = {"t_full_s": t_full, "full_bytes": full_bytes}
    BENCH_RECORDS["masked_copy"] = {
        "grid": [side] * 3,
        "variants": {"full": {"median_s": t_full, "bytes": full_bytes,
                              "executor": "host"}}}
    return _table(
        f"Masked (compressed) transfers, {side}³ × 19 comp (§III-B)",
        rows, ["transfer", "subset", "wire MiB", "link ms @16GB/s",
               "measured pack ms", "wire reduction"])


# ---------------------------------------------------------------------------
# fused vs unfused LB timestep (stencil-aware launch)
# ---------------------------------------------------------------------------

def _pencil_records(grid, reps, steps, devices):
    """The 2×2-pencil fused two_launch lane over ``devices[:4]``, overlap
    off and on: per-variant median step time plus the analytic exchange
    budget (``comm_stats``)."""
    from jax.sharding import Mesh

    from repro.lb.params import LBParams
    from repro.lb.sim import BinaryFluidSim

    p = LBParams(A=0.125, B=0.125, kappa=0.02)
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("px", "py"))
    out = {}
    for key, overlap in (("fused_pencil_2x2", False),
                         ("fused_pencil_2x2_overlap", True)):
        sim = BinaryFluidSim(grid, params=p, fused="two_launch", mesh=mesh,
                             shard_axis=("px", "py"), overlap=overlap)
        st = sim.init_spinodal(seed=0, noise=0.05)
        ws = sim.programs["collide"].step({"f": st.f, "g": st.g})
        exe = sim.programs["fused"]
        ts = _time_stats(lambda: exe.run(dict(ws), steps), reps=reps,
                         warmup=1)
        cs = exe.comm_stats()
        out[key] = {"median_s": ts["median_s"] / steps,
                    "overlap": cs["overlap"],
                    "decomposition": cs["decomposition"],
                    "interior_fraction": cs["interior_fraction"],
                    "exchanged_bytes_per_step":
                        cs["exchanged_bytes_per_step"],
                    "ppermutes_per_step": cs["ppermutes_per_step"]}
    return out


#: child body of the pencil lane on a CPU host: the parent runs its
#: benches on one device, so four forced host devices need their own
#: interpreter.  Prints one JSON doc on the last line.
_SHARDED_BENCH_SRC = r"""
import json, sys
import jax
from benchmarks.run import _pencil_records
grid, reps, steps = json.loads(sys.argv[1])
print(json.dumps(_pencil_records(tuple(grid), reps, steps, jax.devices())))
"""


def _bench_sharded_fused(grid, reps, steps):
    """The pencil lane's records, or ``None`` (with the reason printed)
    where it cannot run.  A chip belongs to one process, so on an
    accelerator the lane runs here, on this process's devices; on a CPU
    host it runs in a child with four forced host devices.  A lane that
    starts and fails raises."""
    import subprocess

    if jax.default_backend() != "cpu":
        devices = jax.devices()
        if len(devices) < 4:
            print(f"[benchmarks] pencil lane not run: it needs 4 devices, "
                  f"this process holds {len(devices)}", file=sys.stderr)
            return None
        return _pencil_records(grid, reps, steps, devices)
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    res = subprocess.run(
        [sys.executable, "-c", _SHARDED_BENCH_SRC,
         json.dumps([list(grid), reps, steps])],
        capture_output=True, text=True, timeout=1200, env=env)
    if res.returncode != 0:
        raise RuntimeError(f"pencil lane failed:\n{res.stderr}")
    return json.loads(res.stdout.strip().splitlines()[-1])


def bench_fused_step(quick=False):
    import warnings

    from repro import tdp
    from repro.lb.params import LBParams
    from repro.lb.sim import BinaryFluidSim

    grid = _grid((16, 16, 16) if quick else (24, 24, 24))
    n = int(np.prod(grid))
    p = LBParams(A=0.125, B=0.125, kappa=0.02)

    # Time the jitted hot-loop body of each regime — since the tdp.Program
    # redesign every regime *is* a compiled Program: the whole unfused
    # timestep (5 stages), the fused stencil stage(s) that replace it —
    # one_launch (radius-2 composed gather), two_launch (streamed-φ
    # intermediate, gather stage (a)) and the gather-free pallas_windowed
    # executor (stage (b); runs in interpret mode on this CPU container,
    # so its wall time measures the Pallas *interpreter*, not the kernel —
    # the claim it carries is the memory structure, reported as the
    # ProgramPlan's aggregated est. HBM bytes).  The extra
    # "fused_program_scan" variant runs K steps under one lax.scan with
    # donated ping-pong field buffers (CompiledProgram.run).
    wt = tdp.Target(WINDOWED)
    sim_u = BinaryFluidSim(grid, params=p)
    sim_f = BinaryFluidSim(grid, params=p, fused="one_launch")
    sim_f2 = BinaryFluidSim(grid, params=p, fused="two_launch")
    sim_w = BinaryFluidSim(grid, params=p, fused="one_launch", target=wt)
    st = sim_u.init_spinodal(seed=0, noise=0.05)
    # pre-stream fused state w = collide(u)
    ws = sim_f.programs["collide"].step({"f": st.f, "g": st.g})

    hbm = {
        "unfused": sim_u.programs["step"].plan().hbm_bytes_estimate(),
        "fused": sim_f.programs["fused"].plan().hbm_bytes_estimate(),
        "fused_two": sim_f2.programs["fused"].plan().hbm_bytes_estimate(),
        "fused_windowed":
            sim_w.programs["fused"].plan().hbm_bytes_estimate(),
    }

    variants = [
        ("unfused pipeline (Program, 5 stages)", "unfused", "xla",
         sim_u.programs["step"].step, ({"f": st.f, "g": st.g},)),
        ("fused (one launch)", "fused", "xla",
         sim_f.programs["fused"].step, (ws,)),
        ("fused (two launches, φ intermediate)", "fused_two", "xla",
         sim_f2.programs["fused"].step, (ws,)),
        (f"fused (windowed, gather-free"
         f"{', interpret' if wt.interpret else ''})", "fused_windowed",
         "pallas_windowed", sim_w.programs["fused"].step, (ws,)),
    ]
    progs = {
        "unfused": sim_u.programs["step"],
        "fused": sim_f.programs["fused"],
        "fused_two": sim_f2.programs["fused"],
        "fused_windowed": sim_w.programs["fused"],
    }
    sweep_keys = {}
    for knob, v, rec_sfx, disp_sfx, s_tgt in _sweep_variants(wt):
        sim_pb = BinaryFluidSim(grid, params=p, fused="one_launch",
                                target=s_tgt)
        key = f"fused_windowed_{rec_sfx}"
        sweep_keys[key] = (knob, v)
        progs[key] = sim_pb.programs["fused"]
        variants.append(
            (f"fused (windowed, {disp_sfx})", key, "pallas_windowed",
             sim_pb.programs["fused"].step, (ws,)))

    rows, rec = [], {"grid": list(grid), "variants": {}}
    base_t = None
    for label, key, executor, fn, args in variants:
        ts = _time_stats(fn, *args,
                         reps=3 if executor == "pallas_windowed" else 5)
        t = ts["median_s"]
        per_site_ns = t / n * 1e9
        rec["variants"][key] = {
            "t_s": t, "ns_per_site_step": per_site_ns, "executor": executor,
            **ts, **({"hbm_bytes_estimate": hbm[key]} if key in hbm else {}),
        }
        if PREDICT and key in progs:
            try:
                est = tdp.predict(progs[key])
            except Exception as e:  # noqa: BLE001 — fidelity tracking
                # must never fail the measurement it annotates
                rec["variants"][key]["predict_error"] = (
                    f"{type(e).__name__}: {e}")
            else:
                rec["variants"][key].update(
                    predicted_s=est.seconds,
                    predicted_vs_measured=(est.seconds - t) / t,
                    predicted_bottleneck=est.bottleneck)
        if key in sweep_keys:
            knob, v = sweep_keys[key]
            rec.setdefault("sweep", {}).setdefault(knob, {})[
                str(v)] = {"median_s": t, **ts}
        if base_t is None:
            base_t = t
        rows.append((label, f"{t*1e3:.2f}", f"{per_site_ns:.1f}",
                     f"{n/t/1e6:.1f}", f"{base_t/t:.2f}×",
                     f"{hbm[key]/2**20:.1f}" if key in hbm else "-"))

    if AUTOTUNE:
        # Close the tuning loop over the fused Program: the default
        # space (windowed plane_block divisor sweep + the xla fallback)
        # measured under the real wall-clock timer; the winner and the
        # full per-candidate report extend this bench's JSON record, and
        # the choice persists in results/tuning/ (a re-run with a warm
        # cache reports cache_hit=True without re-measuring).
        tuned, rep = tdp.autotune(
            sim_w.programs["fused"], example_state=ws,
            measure_steps=1, reps=REPS_OVERRIDE or 3, warmup=1,
            top_k=TOP_K, cache_dir=TUNING_CACHE)
        rec["tuning"] = {"backend": tuned.backend,
                         "interpret": tuned.interpret,
                         **tuned.tuning_dict()}
        rec["autotune"] = rep.as_dict()
        rows.append((f"autotuned → {rep.best.label}"
                     f"{' (cache hit)' if rep.cache_hit else ''}",
                     f"{rep.best_median_s*1e3:.2f}",
                     f"{rep.best_median_s/n*1e9:.1f}",
                     f"{n/rep.best_median_s/1e6:.1f}",
                     f"{rep.default_median_s/rep.best_median_s:.2f}×",
                     "-"))

    # Program-driven scanned variant: K steps in one jitted lax.scan with
    # donated (ping-pong aliased) field buffers; per-step cost amortises
    # the per-call dispatch the .step variants pay.  Donation is a no-op
    # on the CPU backend (XLA warns and falls back) but exercises the
    # real TPU path; each call feeds on the previous call's output.
    K = 10
    exe = sim_f2.programs["fused"]
    holder = {"s": dict(ws)}
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="Some donated buffers")

        def scan_k():
            holder["s"] = exe.run(holder["s"], K, donate=True)
            return holder["s"]

        ts = _time_stats(scan_k, reps=5)
    t = ts["median_s"] / K
    rec["variants"]["fused_program_scan"] = {
        "t_s": t, "ns_per_site_step": t / n * 1e9, "executor": "xla",
        "median_s": t, "min_s": ts["min_s"] / K, "scan_length": K,
        "donated": True, "hbm_bytes_estimate": hbm["fused_two"],
    }
    rows.append((f"fused_two, Program scan×{K} (donated)",
                 f"{t*1e3:.2f}", f"{t/n*1e9:.1f}", f"{n/t/1e6:.1f}",
                 f"{base_t/t:.2f}×", f"{hbm['fused_two']/2**20:.1f}"))

    # Sharded lane: the 2×2-pencil decomposition of the same fused_two
    # step on 4 devices (a CPU host's forced host devices, in a child
    # process; an accelerator's own, in this one), overlap off vs on.
    # The record carries the analytic exchange budget (comm_stats) and
    # the achieved overlap — the fraction of the no-overlap step the
    # interior/boundary split hides.  These CPU numbers demonstrate the
    # *schedule* (collectives per step, bytes on the wire); wall-clock
    # gains need real inter-chip links.
    sharded = _bench_sharded_fused(grid, reps=REPS_OVERRIDE or 3, steps=5)
    if sharded is not None:
        for key, v in sharded.items():
            rec["variants"][key] = {
                **v, "t_s": v["median_s"],
                "ns_per_site_step": v["median_s"] / n * 1e9,
                "executor": "xla", "mesh": "2x2",
            }
            rows.append((f"{key.replace('_', ' ')} (4 devices)",
                         f"{v['median_s']*1e3:.2f}",
                         f"{v['median_s']/n*1e9:.1f}",
                         f"{n/v['median_s']/1e6:.1f}",
                         f"{base_t/v['median_s']:.2f}×", "-"))
        t_off = sharded["fused_pencil_2x2"]["median_s"]
        t_on = sharded["fused_pencil_2x2_overlap"]["median_s"]
        rec["sharded"] = {
            "mesh": [2, 2], "decomposition": "pencil",
            "exchanged_bytes_per_step":
                sharded["fused_pencil_2x2"]["exchanged_bytes_per_step"],
            "ppermutes_per_step":
                sharded["fused_pencil_2x2"]["ppermutes_per_step"],
            "achieved_overlap": 1.0 - t_on / t_off,
        }

    RESULTS["fused_step"] = rec
    BENCH_RECORDS["fused_step"] = rec
    return _table(
        f"Fused vs unfused LB timestep, {grid} lattice ({n} sites)",
        rows, ["implementation", "ms/step", "ns/site·step", "Msites/s",
               "speedup", "est. step HBM MiB (ProgramPlan)"])


# ---------------------------------------------------------------------------
# building-block stencil launches (stream / gradients) across executors
# ---------------------------------------------------------------------------

def _bench_stencil_launch(name, spec, make_input, quick):
    """Shared harness for the single-launch stencil benches: one variant
    per executor (+ optional plane_block sweep for the windowed one),
    with the per-launch HBM estimates alongside."""
    import jax as _jax

    from repro import tdp
    from repro.core import Lattice, launch_plan

    grid = _grid((16, 16, 16) if quick else (24, 24, 24))
    lat = Lattice(grid)
    n = lat.nsites
    x = make_input(lat)

    wt = tdp.Target(WINDOWED)
    targets = [("xla", None, tdp.Target("xla", vvl=128)),
               ("pallas_interpret", None,
                tdp.Target("pallas_interpret", vvl=128)),
               ("pallas_windowed", None, wt)]
    for knob, v, rec_sfx, _disp, s_tgt in _sweep_variants(wt):
        targets.append((f"pallas_windowed_{rec_sfx}", (knob, v), s_tgt))

    rows, rec = [], {"grid": list(grid), "variants": {}}
    for key, swept, tgt in targets:
        fn = _jax.jit(lambda a, t=tgt: tdp.launch(spec, t, a, lattice=lat))
        ts = _time_stats(fn, x, reps=3 if "windowed" in key else 5)
        t = ts["median_s"]
        hbm = launch_plan(spec, tgt, lattice=lat).hbm_bytes_estimate()
        rec["variants"][key] = {
            "t_s": t, "ns_per_site": t / n * 1e9,
            "executor": tgt.executor, **ts, "hbm_bytes_estimate": hbm,
        }
        if swept is not None:
            knob, v = swept
            rec.setdefault("sweep", {}).setdefault(knob, {})[
                str(v)] = {"median_s": t, **ts}
        rows.append((key, f"{t*1e3:.3f}", f"{t/n*1e9:.1f}",
                     f"{n/t/1e6:.1f}", f"{hbm/2**20:.2f}"))
    RESULTS[name] = rec
    BENCH_RECORDS[name] = rec
    return _table(
        f"{name} launch, {grid} lattice ({n} sites)",
        rows, ["executor", "ms/launch", "ns/site", "Msites/s",
               "est. HBM MiB"])


def bench_stream(quick=False):
    """D3Q19 pull streaming (`STREAM_SPEC`) — the pure-gather launch."""
    import jax.numpy as _jnp

    from repro.kernels.lb_collision import NVEL
    from repro.lb.stencil import STREAM_SPEC

    def make(lat):
        rng = np.random.default_rng(0)
        return _jnp.asarray(
            0.05 * rng.normal(size=(NVEL, lat.nsites)) + 1 / 19.,
            _jnp.float32)

    return _bench_stencil_launch("stream", STREAM_SPEC, make, quick)


def bench_grad(quick=False):
    """6-point ∇φ/∇²φ (`GRAD6_SPEC`) — the small-star stencil launch."""
    import jax.numpy as _jnp

    from repro.lb.stencil import GRAD6_SPEC

    def make(lat):
        rng = np.random.default_rng(1)
        return _jnp.asarray(rng.normal(size=(1, lat.nsites)), _jnp.float32)

    return _bench_stencil_launch("grad", GRAD6_SPEC, make, quick)


# ---------------------------------------------------------------------------
# fleet — batched ensemble throughput (steps/sec/device vs batch)
# ---------------------------------------------------------------------------

def bench_fleet(quick=False):
    """Ensemble-execution throughput: one fused LB step graph vmapped
    over batch ∈ {1, 8, 64} (``CompiledProgram.vmap`` — the tdp.fleet
    layer).  The figure of merit is member steps/sec/device.

    On this single-core CPU container the per-member arithmetic cost is
    strictly linear in batch, so the measurable fleet win is the *fixed*
    per-launch cost (host dispatch + XLA prologue) amortised over the
    ensemble — which dominates at service-sized member grids, hence the
    small default lattice.  On a real accelerator the same curve also
    captures idle-parallelism recovery (small members underfill the
    chip), so throughput/device rises with batch until bandwidth
    saturates."""
    from repro import tdp
    from repro.lb.params import LBParams
    from repro.lb.sim import BinaryFluidSim

    grid = _grid((4, 4, 4))
    n = int(np.prod(grid))
    ndev = jax.device_count()
    p = LBParams(A=0.125, B=0.125, kappa=0.02)
    sim = BinaryFluidSim(grid, params=p, fused="two_launch")
    fused = sim.programs["fused"]
    st = sim.init_spinodal(seed=0, noise=0.05)
    ws = sim.programs["collide"].step({"f": st.f, "g": st.g})

    K = 1           # member steps per timed fleet launch
    batches = (1, 8) if quick else (1, 8, 64)
    rows, rec = [], {"grid": list(grid), "scan_length": K,
                     "devices": ndev, "variants": {}}
    for b in batches:
        fleet = fused.vmap(b)
        state = tdp.ProgramState.stack([ws] * b)
        ts = _time_stats(lambda s: fleet.run(s, K), state,
                         reps=REPS_OVERRIDE or 15, warmup=2)
        t = ts["median_s"]
        sps_dev = b * K / t / ndev
        rec["variants"][f"batch{b}"] = {
            **ts, "executor": "xla", "batch": b, "scan_length": K,
            "health": "off",
            "steps_per_s_per_device": sps_dev,
            "msites_per_s": b * K * n / t / 1e6,
        }
        rows.append((b, f"{t*1e3:.2f}", f"{sps_dev:.1f}",
                     f"{b*K*n/t/1e6:.2f}",
                     f"{rec['variants'][f'batch{b}']['steps_per_s_per_device'] / rec['variants']['batch1']['steps_per_s_per_device']:.2f}×"))
    # guard cost: the largest measured batch re-timed with a per-chunk
    # NaN/Inf health check (tdp.HealthPolicy(every=1) — the worst case;
    # every=k amortises this by k).  health_check_overhead is the
    # fractional slowdown vs the unguarded run of the same batch.
    bmax = batches[-1]
    policy = tdp.HealthPolicy(every=1)
    fleet = fused.vmap(bmax)
    state = tdp.ProgramState.stack([ws] * bmax)
    gts = _time_stats(lambda s: fleet.run(s, K, health=policy), state,
                      reps=REPS_OVERRIDE or 15, warmup=2)
    t_off = rec["variants"][f"batch{bmax}"]["median_s"]
    overhead = gts["median_s"] / t_off - 1.0
    rec["variants"][f"batch{bmax}_guarded"] = {
        **gts, "executor": "xla", "batch": bmax, "scan_length": K,
        "health": "every1",
        "steps_per_s_per_device": bmax * K / gts["median_s"] / ndev,
        "msites_per_s": bmax * K * n / gts["median_s"] / 1e6,
        "health_check_overhead": overhead,
    }
    rec["health_check_overhead"] = overhead
    rows.append((f"{bmax} (guarded)", f"{gts['median_s']*1e3:.2f}",
                 f"{bmax*K/gts['median_s']/ndev:.1f}",
                 f"{bmax*K*n/gts['median_s']/1e6:.2f}",
                 f"+{overhead*100:.1f}% guard"))
    RESULTS["fleet"] = rec
    BENCH_RECORDS["fleet"] = rec
    return _table(
        f"Fleet ensemble throughput (fused_two, {grid} lattice, "
        f"{K}-step scans, {ndev} device(s))",
        rows, ["batch", "ms/launch", "member steps/s/device", "Msites/s",
               "throughput/device vs batch=1"])


# ---------------------------------------------------------------------------
# LM pointwise family through tdp backends
# ---------------------------------------------------------------------------

def bench_lm_step(quick=False):
    from repro.kernels import ops

    tokens = 2048 if quick else 8192
    d = 1024
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)

    from repro import tdp

    rows = []
    for name, fn in (
        ("rmsnorm", lambda t: jax.jit(
            lambda xx: ops.rmsnorm(xx, w, target=t))),
        ("swiglu", lambda t: jax.jit(
            lambda xx: ops.gated_act(xx, u, kind="swiglu", target=t))),
    ):
        for backend in ("xla", "pallas_interpret"):
            vvl = 256
            t = _time(fn(tdp.Target(backend, vvl=vvl)), x)
            rows.append((name, backend, vvl, f"{t*1e3:.3f}",
                         f"{tokens/t/1e6:.1f}"))
    RESULTS["lm_pointwise"] = True
    BENCH_RECORDS["lm_step"] = {
        "tokens": tokens,
        "variants": {f"{r[0]}_{r[1]}": {"median_s": float(r[3]) / 1e3,
                                        "executor": r[1], "vvl": r[2]}
                     for r in rows}}
    return _table(
        f"Token-lattice pointwise kernels ({tokens} tokens × d={d})",
        rows, ["kernel", "backend", "VVL", "ms", "Mtok/s"])


# ---------------------------------------------------------------------------
# ported LM kernels (rmsnorm / mamba) — layout × vvl sweep (ISSUE 10)
# ---------------------------------------------------------------------------

def _kernels_record() -> dict:
    """The shared ``BENCH_kernels.json`` record — ``bench_rmsnorm`` and
    ``bench_mamba`` both merge their variants into it, so one committed
    file tracks the whole ported-kernel family."""
    return BENCH_RECORDS.setdefault(
        "kernels", {"variants": {}, "layouts": ["soa", "aosoa"]})


def _layout_vvl_points(quick):
    from repro import tdp
    vvls = (64, 256) if quick else (64, 256, 1024)
    return [(layout, vvl) for layout in tdp.LAYOUTS for vvl in vvls]


def bench_rmsnorm(quick=False):
    """RMSNorm through ``tdp.launch`` (site = token) across
    layout × vvl on the xla executor, plus a ``tdp.autotune`` run over
    the same spec — the record carries the tuner's chosen candidate and
    its default-vs-best medians (the acceptance check that the layout
    axis never costs performance: candidate 0 *is* the SoA default and
    wins ties)."""
    from repro import tdp
    from repro.kernels import lm, ops

    tokens = 2048 if quick else 8192
    d = 1024
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(tokens, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d,)), jnp.float32)

    rec = _kernels_record()
    rec["rmsnorm"] = {"tokens": tokens, "d": d}
    rows = []
    for layout, vvl in _layout_vvl_points(quick):
        tgt = tdp.Target("xla", vvl=vvl, layout=layout)
        fn = jax.jit(lambda xx, t=tgt: ops.rmsnorm(xx, w, target=t))
        ts = _time_stats(fn, x)
        key = f"rmsnorm_xla_{layout}_vvl{vvl}"
        rec["variants"][key] = {**ts, "executor": "xla", "vvl": vvl,
                                "layout": layout, "kernel": "rmsnorm",
                                "sites": tokens}
        rows.append(("rmsnorm", layout, vvl, f"{ts['median_s']*1e3:.3f}",
                     f"{tokens/ts['median_s']/1e6:.1f}"))

    spec = lm.rmsnorm_spec(d)
    consts = {"weight": w, "eps": 1e-6, "scale_offset": 0.0}
    tuned, rep = tdp.autotune(
        spec, tdp.Target("xla", vvl=256), (x.T,), consts=consts,
        reps=REPS_OVERRIDE or 3, warmup=1, cache_dir=TUNING_CACHE)
    rec["autotune_rmsnorm"] = {
        "best": rep.best.label,
        "default_median_s": rep.default_median_s,
        "best_median_s": rep.best_median_s,
        "layout": tuned.layout, "vvl": tuned.vvl,
    }
    rows.append((f"rmsnorm autotuned → {rep.best.label}", tuned.layout,
                 tuned.vvl or "-", f"{rep.best_median_s*1e3:.3f}",
                 f"{rep.default_median_s/rep.best_median_s:.2f}× vs default"))
    return _table(
        f"RMSNorm layout×VVL sweep ({tokens} tokens × d={d}, xla)",
        rows, ["kernel", "layout", "VVL", "ms", "Mtok/s"])


def bench_mamba(quick=False):
    """Selective-scan (site = channel, time on the component axis)
    across layout × vvl on the xla executor — the recurrent member of
    the ported family; the layout axis regroups the *channel* sites."""
    from repro import tdp
    from repro.kernels import ops

    length, d_inner, nstate = (64, 256, 8) if quick else (128, 512, 16)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, length, d_inner)), jnp.float32)
    dt = jnp.asarray(
        0.1 + 0.9 * rng.random((1, length, d_inner)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1, length, nstate)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(1, length, nstate)), jnp.float32)
    a = jnp.asarray(-0.5 - rng.random((d_inner, nstate)), jnp.float32)
    dd = jnp.asarray(rng.normal(size=(d_inner,)), jnp.float32)

    rec = _kernels_record()
    rec["mamba"] = {"length": length, "d_inner": d_inner,
                    "nstate": nstate}
    rows = []
    for layout, vvl in _layout_vvl_points(quick):
        tgt = tdp.Target("xla", vvl=vvl, layout=layout)
        fn = jax.jit(lambda *args, t=tgt: ops.mamba_scan(*args, target=t))
        ts = _time_stats(fn, x, dt, b, c, a, dd)
        key = f"mamba_xla_{layout}_vvl{vvl}"
        rec["variants"][key] = {**ts, "executor": "xla", "vvl": vvl,
                                "layout": layout, "kernel": "mamba_scan",
                                "scan_length": length, "sites": d_inner}
        rows.append(("mamba_scan", layout, vvl,
                     f"{ts['median_s']*1e3:.3f}",
                     f"{length*d_inner/ts['median_s']/1e6:.1f}"))
    return _table(
        f"Mamba selective scan layout×VVL sweep "
        f"(L={length}, d={d_inner}, N={nstate}, xla)",
        rows, ["kernel", "layout", "VVL", "ms", "Mcell/s"])


BENCHES = {
    "fig1": bench_fig1,
    "vvl": bench_vvl,
    "masked_copy": bench_masked_copy,
    "fused_step": bench_fused_step,
    "stream": bench_stream,
    "grad": bench_grad,
    "fleet": bench_fleet,
    "lm_step": bench_lm_step,
    "rmsnorm": bench_rmsnorm,
    "mamba": bench_mamba,
}


def _parse_sweep(text: str) -> dict[str, list]:
    """``"plane_block=1,2,4"`` → ``{"plane_block": [1, 2, 4]}``.

    Any ``Target.tuning`` knob parses (values as ints where possible);
    whether the swept executor actually *consumes* the knob is validated
    against its declared tunables in :func:`main` — a silently ignored
    sweep would read as "ran"."""
    out: dict[str, list] = {}
    for part in text.split(";"):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(f"--sweep expects key=v1,v2,...; got {part!r}")
        key, vals = part.split("=", 1)
        key = key.strip()
        if not key:
            raise ValueError(f"--sweep has an empty knob name: {part!r}")
        values = []
        for v in vals.split(","):
            v = v.strip()
            if not v:
                continue
            try:
                values.append(int(v))
            except ValueError:
                raise ValueError(
                    f"--sweep {key}= values must be integers, got {v!r}")
        if not values:
            raise ValueError(f"--sweep {key}= has no values")
        out[key] = values
    return out


#: benches that consult SWEEPS — a --sweep whose --only selection hits
#: none of them would silently no-op, so main() rejects that combination.
SWEEP_CONSUMERS = ("fused_step", "stream", "grad")


def main(argv=None):
    global AUTOTUNE, GRID_OVERRIDE, REPS_OVERRIDE, TUNING_CACHE
    global TOP_K, PREDICT, WINDOWED
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, metavar="NAME[,NAME...]",
                    help=f"comma-separated subset of {sorted(BENCHES)}")
    ap.add_argument("--out", default="results/bench")
    ap.add_argument("--json", action="store_true",
                    help="also write one BENCH_<name>.json per bench run "
                         "(machine-readable perf trajectory) under --out")
    ap.add_argument("--sweep", default=None, metavar="KEY=V1,V2,...",
                    help="sweep any Target.tuning knob the windowed "
                         "executor declares (e.g. plane_block=1,2,4) over "
                         "its bench variants; per-value medians land in "
                         "the bench JSON under 'sweep'; an undeclared "
                         "knob exits 2")
    ap.add_argument("--autotune", action="store_true",
                    help="run tdp.autotune over bench_fused_step's fused "
                         "Program; the tuned choice + TuneReport extend "
                         "BENCH_fused_step.json ('tuning'/'autotune' "
                         "keys) and persist in the --tuning-cache dir")
    ap.add_argument("--grid", type=int, default=None, metavar="N",
                    help="override the lattice side (N³) for the grid "
                         "benches — smoke runs")
    ap.add_argument("--steps", type=int, default=None, metavar="K",
                    help="override timing repetitions per variant (and "
                         "autotune reps) — smoke runs")
    ap.add_argument("--windowed", default=WINDOWED,
                    choices=("pallas_windowed", "pallas_windowed_interpret"),
                    help="executor of the windowed variants: compiled by "
                         "Mosaic (TPU only) or the Pallas interpreter")
    ap.add_argument("--tuning-cache", default="results/tuning",
                    help="tdp.autotune on-disk cache directory")
    ap.add_argument("--top-k", type=int, default=None, metavar="K",
                    help="with --autotune: measure only the base target "
                         "plus the K best candidates by the cost model's "
                         "predicted time (model-pruned candidates are "
                         "recorded in the report, not dropped)")
    ap.add_argument("--predict", action="store_true",
                    help="annotate bench_fused_step variants with the "
                         "cost model's predicted step time "
                         "(predicted_s / predicted_vs_measured)")
    args = ap.parse_args(argv)

    if args.grid is not None:
        if args.grid <= 0:
            print("[benchmarks] --grid must be positive", file=sys.stderr)
            return 2
        GRID_OVERRIDE = args.grid
    if args.steps is not None:
        if args.steps <= 0:
            print("[benchmarks] --steps must be positive", file=sys.stderr)
            return 2
        REPS_OVERRIDE = args.steps
    AUTOTUNE = bool(args.autotune)
    WINDOWED = args.windowed
    TUNING_CACHE = args.tuning_cache
    TOP_K = args.top_k
    PREDICT = bool(args.predict)
    if TOP_K is not None and TOP_K <= 0:
        print("[benchmarks] --top-k must be positive", file=sys.stderr)
        return 2
    if TOP_K is not None and not AUTOTUNE:
        print("[benchmarks] --top-k only applies with --autotune",
              file=sys.stderr)
        return 2

    if args.only:
        selected = [s.strip() for s in args.only.split(",") if s.strip()]
        unknown = sorted(set(selected) - set(BENCHES))
        if unknown:
            print(f"[benchmarks] unknown bench name(s): "
                  f"{', '.join(unknown)}; available: "
                  f"{', '.join(sorted(BENCHES))}", file=sys.stderr)
            return 2
    else:
        selected = list(BENCHES)

    if AUTOTUNE and "fused_step" not in selected:
        print("[benchmarks] --autotune runs inside bench_fused_step, which "
              "the --only selection excludes", file=sys.stderr)
        return 2

    if args.sweep:
        try:
            SWEEPS.update(_parse_sweep(args.sweep))
        except ValueError as e:
            print(f"[benchmarks] {e}", file=sys.stderr)
            return 2
        from repro.core import executor_tunables
        declared = executor_tunables(SWEEP_EXECUTOR)
        ignored = sorted(set(SWEEPS) - set(declared))
        if ignored:
            print(f"[benchmarks] --sweep knob(s) {', '.join(ignored)} are "
                  f"ignored by executor {SWEEP_EXECUTOR!r}; declared "
                  f"tunables: {', '.join(declared) or '(none)'}",
                  file=sys.stderr)
            return 2
        if not set(selected) & set(SWEEP_CONSUMERS):
            print(f"[benchmarks] --sweep has no effect: none of the "
                  f"selected benches ({', '.join(sorted(selected))}) "
                  f"consume it; sweep-aware benches: "
                  f"{', '.join(SWEEP_CONSUMERS)}", file=sys.stderr)
            return 2

    texts = [fn(args.quick) for name, fn in BENCHES.items()
             if name in selected]

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "bench_results.json"), "w") as fh:
        json.dump({k: v for k, v in RESULTS.items()
                   if not k.startswith("fig1_vvl")}, fh, indent=1,
                  default=str)
    with open(os.path.join(args.out, "bench_tables.md"), "w") as fh:
        fh.write("\n".join(texts))
    if args.json:
        for name, rec in BENCH_RECORDS.items():
            path = os.path.join(args.out, f"BENCH_{name}.json")
            with open(path, "w") as fh:
                json.dump({"bench": name, "quick": args.quick, **rec}, fh,
                          indent=1, default=str)
            print(f"[benchmarks] wrote {path}")
    print(f"\n[benchmarks] tables + JSON written to {args.out}/")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
