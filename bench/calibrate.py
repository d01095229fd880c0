"""Readings from which the limits of `correct` are set, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--record-trace DIR] --out FILE

In one process, for each seed, a run of the cell as `bench/run.py` makes
it (`harness.run_cell`) with a window that closes at the kept call: the
numbers it compares are the lower readings.  For each control seed, the
same run with each control in the program's place (the plain reference
in the precisions one tier below the configuration's,
`harness.control_build`): its numbers are the upper readings, and its
`correct` has to come out false.  `--record-trace DIR` also writes two
traced windows, each as the profiler's file and reduced to its events:
one as the benchmark traces it, and a short one of three-step calls for
the tests of the reduction.  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

CONTROLS = ("high", "bf16_state")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--record-trace", default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench.lib import harness, lattice, registry, trace as tr

    harness.enable_compile_cache(ROOT)
    bench = registry.load_benchmark(ROOT)
    cell = registry.cell(bench, args.workload)
    cfg = registry.load_config(ROOT, cell["config"])
    traffic = registry.load_traffic(ROOT, cell["traffic"])
    devices = jax.devices()[:int(cell["chips"])]
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print("[calibrate] needs the cell's TPU chips", file=sys.stderr)
        return 1
    sim = lattice.build(cfg, devices)
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "program": [], "controls": {c: [] for c in CONTROLS}}

    def one(seed, label, build):
        t0 = time.perf_counter()
        res = harness.run_cell(ROOT, args.workload, seed, 0.0, False, t0,
                               log=lambda line: None, build=build)
        rec = {"seed": seed, "run": label, "correct": res["correct"],
               **{k: c["value"] for k, c in res["check"].items()},
               "seconds": time.perf_counter() - t0}
        print(f"[calibrate] {json.dumps(rec)}", flush=True)
        return rec

    def program(cfg, devices):
        return sim

    for seed in [int(s) for s in args.seeds.split(",")]:
        out["program"].append(one(seed, "program", program))
    for seed in [int(s) for s in args.control_seeds.split(",") if s]:
        for prec in CONTROLS:
            out["controls"][prec].append(one(seed, prec, harness.control_build(prec, program)))

    if args.record_trace:
        # a window as the benchmark traces it, and a short one of three-step calls
        for label, mix, seconds in (("full", traffic, 6.0),
                                    ("short", dict(traffic, steps_per_call=3), 0.0)):
            state = lattice.init_state(cfg, sim, 7, registry.load_reference(cfg["reference"]))
            state, _ = harness.run_window(sim, state, mix, 0.0, keep=0)
            tdir = os.path.join(args.record_trace, f"raw_{label}")
            jax.profiler.start_trace(tdir)
            state, win = harness.run_window(sim, state, mix, seconds, keep=1, spans=True)
            jax.profiler.stop_trace()
            del state
            t = tr.from_xplane(tr.find_xplane(tdir), {d.id for d in devices})
            stem = os.path.join(args.record_trace, f"{args.workload}.{label}")
            t.to_json(stem + ".trace.json.gz")
            with open(stem + ".ctx.json", "w") as fh:
                json.dump({"calls": win.calls, "steps": win.steps,
                           "kernel_steps": lattice.kernel_steps(cfg, win.calls,
                                                                mix["steps_per_call"]),
                           "chips": len(devices), "sites_per_chip": harness.sites_per_chip(cfg),
                           "device_kind": devices[0].device_kind}, fh)

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"[calibrate] done in {time.perf_counter() - T_START:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
