"""Spinodal decomposition as a service — a fleet of binary-fluid
trajectories behind ``tdp.FleetDriver``.

Each "client" submits one quench with its own random seed and its own
mobility (a ``tau_phi`` sweep): the driver batches every request into a
single vmapped fleet step (one jit for the whole sweep — per-member
constants ride along as traced operands, so new parameter values never
recompile), streams progress snapshots back per ticket, and optionally
checkpoints all in-flight trajectories so a killed service resumes every
ticket bit-exactly.

Run:  PYTHONPATH=src python examples/lb_fleet.py [--batch 4 --steps 40]
CI smoke: --batch 4 --steps 2 --grid 8
"""
import argparse
import sys, os, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import tdp
from repro.launch.cache import enable_compile_cache
from repro.lb import programs as lbp
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=16)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=4,
                    help="fleet slots per bucket (also the number of "
                         "submitted trajectories here)")
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--vvl", type=int, default=128)
    ap.add_argument("--stream-every", type=int, default=0,
                    help="print φ-variance snapshots of ticket 0 every "
                         "k member steps (0 = off)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="checkpoint all in-flight tickets here "
                         "(kill + rerun with the same dir resumes them)")
    ap.add_argument("--chaos", action="store_true",
                    help="failure drill: guard all trajectories with a "
                         "HealthPolicy and poison one ticket's g field "
                         "mid-run — the driver quarantines exactly that "
                         "member while the rest complete")
    args = ap.parse_args()

    grid = (args.grid,) * 3
    params = LBParams(A=0.125, B=0.125, kappa=0.02)

    # The served step graph: the unfused LB step with tau_phi (mobility)
    # left as a per-ticket sweep value.  Clients bind their own value in
    # params["consts"]; the driver turns the spread into one BatchedConst
    # bucket.
    phys = params.as_kwargs()
    prog = lbp.unfused_step_program(
        lbp.collision_consts(np.float32, **phys))

    # seed states come from the sim helper (equilibrium populations of a
    # noisy quench), one seed per client
    sim = BinaryFluidSim(grid, params=params,
                         target=tdp.Target(args.backend, vvl=args.vvl))

    # resume-or-fresh: the driver creates checkpoint_dir on construction,
    # so "does the dir exist" can't distinguish a prior run — try the
    # restore and fall back when no checkpoint has been written yet.
    drv, resumed = None, {}
    if args.checkpoint_dir:
        try:
            drv = tdp.FleetDriver.restore(args.checkpoint_dir, prog,
                                          batch=args.batch,
                                          checkpoint_every=4)
            resumed = dict(drv._tickets)
            print(f"[lb_fleet] resumed {len(resumed)} ticket(s) from "
                  f"{args.checkpoint_dir}")
        except FileNotFoundError:
            pass
    health = tdp.HealthPolicy(fields=("g",), every=2) if args.chaos \
        else None
    if drv is None:
        drv = tdp.FleetDriver(tdp.Target(args.backend, vvl=args.vvl),
                              batch=args.batch,
                              checkpoint_dir=args.checkpoint_dir,
                              checkpoint_every=4 if args.checkpoint_dir
                              else None,
                              health=health)

    tau_phis = np.linspace(0.8, 1.2, args.batch).astype(np.float32)
    tickets = list(resumed.values())
    if not tickets:
        for i in range(args.batch):
            st = sim.init_spinodal(seed=i, noise=0.05)
            t = drv.submit(prog,
                           {"state": {"f": st.f, "g": st.g},
                            "consts": {"tau_phi": tau_phis[i]}},
                           args.steps)
            tickets.append(t)
            print(f"[lb_fleet] submitted {t.id}: seed {i}, "
                  f"tau_phi {tau_phis[i]:.2f}, {args.steps} steps")

    def phi_var(state):
        phi = np.asarray(state["g"]).sum(axis=0)
        return float(phi.var())

    victim = None
    if args.chaos and len(tickets) >= 2:
        from repro.core import faults
        victim = tickets[1]
        poison_at = max(1, args.steps // 2)
        drv.inject(faults.nan_at_step(victim.id, "g", poison_at))
        print(f"[lb_fleet] chaos: poisoning {victim.id} field 'g' at "
              f"member step {poison_at} (guard: NaN/Inf every 2 steps)")

    t0 = time.perf_counter()
    if args.stream_every:
        for step, snap in drv.stream(tickets[0], every=args.stream_every):
            print(f"[lb_fleet] {tickets[0].id} step {step:>5}: "
                  f"phi_var {phi_var(snap):.5f}")
    final = drv.drain()
    dt = time.perf_counter() - t0

    nsites = args.grid ** 3
    done_steps = sum(t.nsteps for t in tickets)
    print(f"[lb_fleet] {len(tickets)} trajectories x {args.steps} steps "
          f"on {args.grid}^3 in {dt:.2f}s "
          f"({done_steps * nsites / dt / 1e6:.2f} Msites/s aggregate, "
          f"{len(drv._buckets)} bucket jit(s))")
    for t in tickets:
        p = drv.poll(t)
        if victim is not None and t.id == victim.id:
            assert p["status"] == "failed", \
                f"{t.id}: expected quarantine, got {p['status']}"
            assert isinstance(p["error"], tdp.HealthError)
            print(f"[lb_fleet] {t.id}: quarantined -> {p['error']}")
            continue
        assert p["done"] and p["step"] == t.nsteps
        var = phi_var(final[t.id])
        assert np.isfinite(var), f"{t.id}: non-finite fields"
        print(f"[lb_fleet] {t.id}: tau_phi "
              f"{float(np.asarray(t.consts['tau_phi'])):.2f} -> "
              f"phi_var {var:.5f}")
    print("[lb_fleet] OK")


if __name__ == "__main__":
    main()
