import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# The two lines above MUST run before any other import (jax locks the
# device count at first initialisation).  Do not reorder.
"""Multi-pod AOT dry-run.

For every (architecture × input-shape × mesh) cell:
``jax.jit(step, in_shardings, out_shardings).lower(*abstract).compile()``
on 512 placeholder CPU devices, then record

  * ``memory_analysis()``   — per-chip argument/output/temp bytes (fits?),
  * ``cost_analysis()``     — HLO FLOPs + bytes for §Roofline,
  * collective bytes parsed from the post-SPMD HLO (per opcode),
  * wall compile time,

into one JSON per cell under ``results/dryrun/`` (resumable cache — rerun
skips completed cells unless --force).

Usage:
  python -m repro.launch.dryrun --mesh both                  # all cells
  python -m repro.launch.dryrun --arch gemma3-27b --shape decode_32k \
         --mesh single --variant baseline
"""
import argparse
import dataclasses
import json
import time
import traceback

# the static HLO analysis (collective byte scan + trip-count-exact
# walker) lives in the cost-model subsystem now; stdlib-only import, so
# it is safe before jax initialises
from repro.core.costmodel import analyze, collective_bytes  # noqa: F401


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    d = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            d[k] = int(v)
    if not d:
        d["repr"] = str(mem)
    return d


def run_cell(arch: str, shape: str, mesh_name: str, variant, out_dir: str,
             force: bool = False) -> dict:
    """Build, lower, compile, analyse one cell.  Returns the record."""
    import jax
    from repro.launch.mesh import make_production_mesh
    from repro.launch.cells import build_cell

    vtag = variant.name
    fname = f"{arch}__{shape}__{mesh_name}__{vtag}.json".replace("/", "_")
    path = os.path.join(out_dir, fname)
    if os.path.exists(path) and not force:
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") == "ok":
            return rec

    os.makedirs(out_dir, exist_ok=True)
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
           "variant": dataclasses.asdict(variant),
           "n_devices": mesh.devices.size}
    t0 = time.monotonic()
    try:
        cell = build_cell(arch, shape, mesh, variant)
        rec["model_flops"] = cell.model_flops
        rec["kind"] = cell.kind
        jfn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                      out_shardings=cell.out_shardings,
                      donate_argnums=cell.donate)
        lowered = jfn.lower(*cell.args)
        rec["lower_s"] = time.monotonic() - t0
        t1 = time.monotonic()
        compiled = lowered.compile()
        rec["compile_s"] = time.monotonic() - t1
        ca = compiled.cost_analysis() or {}
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float)) and
                                not k.startswith(("utilization",
                                                  "bytes accessed"))}
        rec["memory_analysis"] = _mem_dict(compiled.memory_analysis())
        hlo_text = compiled.as_text()
        rec["collectives"] = collective_bytes(hlo_text)
        # trip-count-exact static analysis (XLA's cost_analysis counts scan
        # bodies once — see the repro.core.costmodel walker docstring)
        rec["hlo_analysis"] = analyze(hlo_text)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — recorded, reported, non-zero exit
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = time.monotonic() - t0
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def iter_cells(archs, shapes, meshes):
    from repro import configs as C
    for arch in archs:
        for shape, skip in C.applicable_cells(arch):
            if shapes and shape not in shapes:
                continue
            if skip:
                yield arch, shape, None, skip
                continue
            for mesh_name in meshes:
                yield arch, shape, mesh_name, ""


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=("single", "multi",
                                                       "both"))
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--set", nargs="*", default=[],
                    help="variant overrides, e.g. --set grad_accum=8 "
                         "fsdp=false attn_impl=ref")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    from repro import configs as C
    from repro.launch.cells import Variant

    archs = list(C.ARCHS) if args.arch == "all" else \
        [C.ALIASES.get(args.arch, args.arch)]
    shapes = None if args.shape == "all" else {args.shape}
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        fld = {f.name: f for f in dataclasses.fields(Variant)}[k]
        if fld.type in ("int",):
            v = int(v)
        elif fld.type in ("bool",):
            v = v.lower() in ("1", "true", "yes")
        overrides[k] = v
    variant = Variant(name=args.variant, **overrides) \
        if overrides else Variant(name=args.variant)

    plan = list(iter_cells(archs, shapes, meshes))
    if args.list:
        for arch, shape, mesh_name, skip in plan:
            print(f"{arch:24s} {shape:12s} "
                  f"{mesh_name or '-':7s} {'SKIP: ' + skip if skip else ''}")
        return 0

    failures = 0
    for arch, shape, mesh_name, skip in plan:
        if skip:
            print(f"[dryrun] {arch} × {shape}: SKIP ({skip.split('(')[0]})",
                  flush=True)
            continue
        print(f"[dryrun] {arch} × {shape} × {mesh_name} "
              f"[{variant.name}] ...", flush=True)
        rec = run_cell(arch, shape, mesh_name, variant, args.out,
                       force=args.force)
        if rec["status"] == "ok":
            ha = rec["hlo_analysis"]
            mem = rec["memory_analysis"]
            per_dev = (mem.get("argument_size_in_bytes", 0) +
                       mem.get("temp_size_in_bytes", 0))
            print(f"  ok in {rec['total_s']:.1f}s  "
                  f"TF/dev={ha['flops']/1e12:.2f}  "
                  f"mem/dev={per_dev/2**30:.2f}GiB  "
                  f"traffic={ha['traffic_bytes']/2**30:.1f}GiB  "
                  f"ici={ha['wire_bytes_ici']/2**30:.2f}GiB "
                  f"dcn={ha['wire_bytes_dcn']/2**30:.2f}GiB",
                  flush=True)
        else:
            failures += 1
            print(f"  ERROR: {rec['error']}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
