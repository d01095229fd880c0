"""Spinodal decomposition of a binary fluid — the Ludwig-style application.

A symmetric quench (φ = ±noise) phase-separates into domains; this is the
physics the paper's binary-collision benchmark kernel comes from.  Runs
the full targetDP-structured simulation (moments → stencil → collision →
streaming), each regime a compiled ``tdp.Program`` step graph — the
chunked stepping below goes through ``CompiledProgram.run``'s single
``lax.scan`` (``--donate`` ping-pongs the field buffers) — and prints
conservation + coarsening observables plus the aggregated per-step HBM
estimate from ``ProgramPlan``.

Run:  PYTHONPATH=src python examples/lb_spinodal.py [--steps 400]
"""
import argparse
import sys, os, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import tdp
from repro.launch.cache import enable_compile_cache
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=16)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--chunk", type=int, default=50)
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas", "pallas_interpret",
                             "pallas_windowed", "pallas_windowed_interpret"),
                    help="pallas_windowed* is stencil-only (gather-free "
                         "windowed executor) — pair it with --fused; the "
                         "sim's pointwise collision falls back to xla")
    ap.add_argument("--vvl", type=int, default=128)
    ap.add_argument("--fused", nargs="?", const="one_launch", default=False,
                    choices=("one_launch", "two_launch"),
                    help="fused stream+gradient+collide stencil launch(es) "
                         "per step (same trajectory): one_launch = radius-2 "
                         "composed stencil; two_launch = streamed-phi "
                         "intermediate (lower gather footprint)")
    ap.add_argument("--donate", action="store_true",
                    help="donate the hot-loop field buffers in each "
                         "scanned chunk (ping-pong aliasing; no per-step "
                         "reallocation)")
    ap.add_argument("--mesh", default=None, metavar="NxM[xK]",
                    help="shard the grid over the process's devices: "
                         "'4' = slab, '2x2' = pencil, '2x2x2' = block "
                         "(mesh axis k shards grid dim k; run under "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=N to fake devices on CPU)")
    ap.add_argument("--overlap", action="store_true",
                    help="launch each stage's interior while the ghost "
                         "exchanges are in flight (sharded runs; "
                         "trajectories match to ~1 ULP, not bitwise — "
                         "see docs/targetdp_api.md)")
    args = ap.parse_args()

    mesh = None
    shard_axis = "data"
    if args.mesh:
        from repro.launch.mesh import make_test_mesh
        shape = tuple(int(s) for s in args.mesh.lower().split("x"))
        shard_axis = tuple(f"p{'xyz'[d]}" for d in range(len(shape)))
        mesh = make_test_mesh(shape, shard_axis)
        print(f"[lb_spinodal] mesh {dict(zip(shard_axis, shape))}: "
              f"{'slab pencil block'.split()[len(shape) - 1]} "
              f"decomposition")

    params = LBParams(A=0.125, B=0.125, kappa=0.02)
    sim = BinaryFluidSim((args.grid,) * 3, params=params,
                         target=tdp.Target(args.backend, vvl=args.vvl),
                         fused=args.fused, mesh=mesh, shard_axis=shard_axis,
                         overlap=args.overlap)
    hot = sim.programs["fused" if args.fused else "step"]
    plan = hot.plan()
    print(f"[lb_spinodal] hot-loop Program "
          f"{hot.program.name!r}: stages "
          f"{[r['stage'] + '@' + r['executor'] for r in plan.per_stage()]}, "
          f"est. per-step HBM {plan.hbm_bytes_estimate() / 2**20:.1f} MiB")
    if mesh is not None:
        cs = hot.comm_stats()
        print(f"[lb_spinodal] exchange schedule {hot.exchange_schedule}: "
              f"{cs['exchanged_bytes_per_step'] / 2**10:.1f} KiB and "
              f"{cs['ppermutes_per_step']} ppermutes per step"
              + (f"; overlap interior fraction "
                 f"{cs['interior_fraction']:.2f}" if cs["overlap"] else ""))
    state = sim.init_spinodal(seed=0, noise=0.05)

    obs0 = sim.observables(state)
    print(f"{'step':>6} {'mass':>12} {'phi_total':>12} {'phi_var':>10} "
          f"{'phi_range':>16} {'Msites/s':>9}")

    def report(st, rate=0.0):
        o = sim.observables(st)
        print(f"{st.step:>6} {o['mass']:>12.4f} {o['phi_total']:>12.5f} "
              f"{o['phi_var']:>10.5f} "
              f"[{o['phi_min']:>6.3f},{o['phi_max']:>6.3f}] "
              f"{rate:>9.2f}")
        assert not o["nan"], "NaN in fields"
        return o

    report(state)
    n = sim.grid_shape[0] ** 3
    while state.step < args.steps:
        chunk = min(args.chunk, args.steps - state.step)
        t0 = time.perf_counter()
        state = sim.run(state, chunk, donate=args.donate)
        state.f.block_until_ready()
        dt = time.perf_counter() - t0
        report(state, rate=n * chunk / dt / 1e6)

    o_end = sim.observables(state)
    drift = abs(o_end["mass"] - obs0["mass"]) / obs0["mass"]
    print(f"\n[lb_spinodal] mass drift over {args.steps} steps: {drift:.2e}")
    print(f"[lb_spinodal] φ variance {obs0['phi_var']:.5f} → "
          f"{o_end['phi_var']:.5f} (domains formed)")


if __name__ == "__main__":
    main()
