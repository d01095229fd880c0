"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  `--trace 0` measures the cell's end-to-end
metrics; `--trace 1` runs the same window under the profiler and reports
its per-layer metrics.  Both decide `correct` by comparing one call of the
window with the plain reference.  Lines before the last are information;
the last line of standard output is the result, one JSON object, and the
last lines of standard error are the compared numbers beside their limits.
A run that finds no TPU, or fewer chips than the cell asks for, exits 1
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# At start-up the TPU runtime maps a host buffer for transfers, 4 GiB unless
# told otherwise; on small pages (transparent hugepages off) that takes 6-9 s
# of the set-up and swings by 3 s from run to run.  A run moves only
# programs and statistics between host and chip, so 256 MiB holds them.
PREMAPPED_BYTES = str(256 << 20)
os.environ["TPU_PREMAPPED_BUFFER_SIZE"] = PREMAPPED_BYTES
os.environ["TPU_PREMAPPED_BUFFER_TRANSFER_THRESHOLD_BYTES"] = PREMAPPED_BYTES


def _log(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench.lib import harness, registry

    harness.stamp(_log, T_START, "import")
    cell = registry.cell(registry.load_benchmark(ROOT), args.workload)
    devices = jax.devices()
    harness.stamp(_log, T_START, "devices")
    if devices[0].platform != "tpu":
        _log(f"[bench] no TPU: JAX's first device is {devices[0].platform!r}; "
             f"the benchmark has no CPU path")
        return 1
    if len(devices) < int(cell["chips"]):
        _log(f"[bench] cell {args.workload!r} needs {cell['chips']} chips, "
             f"JAX found {len(devices)}")
        return 1
    harness.enable_compile_cache(ROOT)

    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START, log=_log)
    for name, c in result["check"].items():
        _log(f"[bench] check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
