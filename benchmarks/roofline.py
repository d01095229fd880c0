"""Roofline analysis from the dry-run artifacts (deliverable g).

Reads ``results/dryrun/*.json`` (written by ``repro.launch.dryrun``) and
derives, per (arch × shape × mesh × variant):

  T_compute    = HLO_FLOPs / peak_FLOPs            (197 TF bf16 / chip)
  T_memory     = HLO_traffic_bytes / HBM_bw        (819 GB/s / chip)
  T_collective = wire_bytes_ici / ICI_bw  (+ DCN)  (50 GB/s/link; DCN 25)

All three inputs are **per-chip** (the post-SPMD module is per-chip) and
**trip-count exact** (see the ``repro.core.costmodel`` HLO walker —
XLA's own cost_analysis undercounts scan bodies by their trip counts).
The arithmetic itself lives in
:func:`repro.core.costmodel.dryrun_record_terms`; this module is the
table/CLI view over it.

Additional columns:
  MODEL_FLOPS        6·N·D (dense) / 6·N_active·D (MoE); 2·N·D serving
  useful ratio       MODEL_FLOPS / (HLO_FLOPs · chips) — remat/masking/
                     capacity-dispatch waste shows up here
  bottleneck         argmax of the three terms
  roofline fraction  T_dominant / ΣT — how balanced the cell is; the §Perf
                     loop drives the dominant term down
  fits               per-chip arguments+temp ≤ 16 GB HBM

Usage:
  PYTHONPATH=src python -m benchmarks.roofline [--mesh single]
      [--variant baseline] [--md results/roofline.md]
"""
from __future__ import annotations

import argparse
import glob
import json
import os

from repro.core.costmodel import MachineProfile, dryrun_record_terms

# TPU v5e table rates — kept as module constants for scripts that import
# them, but sourced from (and asserted against) the cost model's profile
# table so the two can never drift apart.
_PROFILE = MachineProfile.default("tpu:TPU v5 lite")
PEAK_FLOPS = _PROFILE.peak_flops   # 197e12  bf16
HBM_BW = _PROFILE.hbm_bw           # 819e9   bytes/s
ICI_BW = _PROFILE.link_bw          # 200e9   bytes/s chip-to-chip
DCN_BW = _PROFILE.dcn_bw           # 25e9    bytes/s cross-pod
HBM_BYTES = _PROFILE.hbm_bytes     # 16 GB


def load_records(out_dir="results/dryrun", mesh=None, variant=None):
    recs = []
    for f in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        rec = json.load(open(f))
        if rec.get("status") != "ok":
            continue
        if mesh and rec["mesh"] != mesh:
            continue
        if variant and rec["variant"]["name"] != variant:
            continue
        recs.append(rec)
    return recs


def terms(rec):
    return dryrun_record_terms(rec, _PROFILE)


def fmt_s(x):
    if x >= 1.0:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}µs"


def table(recs, *, md=False):
    headers = ["arch", "shape", "mesh", "variant", "T_comp", "T_mem",
               "T_coll", "bottleneck", "useful", "GiB/dev", "fits"]
    rows = []
    for rec in sorted(recs, key=lambda r: (r["arch"], r["shape"],
                                           r["mesh"])):
        t = terms(rec)
        rows.append([
            rec["arch"], rec["shape"], rec["mesh"],
            rec["variant"]["name"],
            fmt_s(t["t_compute"]), fmt_s(t["t_memory"]),
            fmt_s(t["t_collective"]),
            f"{t['dominant']} ({t['frac']:.0%})",
            f"{t['useful_ratio']:.2f}",
            f"{t['bytes_per_dev']/2**30:.1f}",
            "✓" if t["fits"] else "✗",
        ])
    if md:
        out = ["| " + " | ".join(headers) + " |", "|" + "---|" * len(headers)]
        out += ["| " + " | ".join(str(c) for c in r) + " |" for r in rows]
    else:
        w = [max(len(str(r[i])) for r in rows + [headers])
             for i in range(len(headers))]
        out = ["  ".join(h.ljust(w[i]) for i, h in enumerate(headers))]
        out += ["  ".join(str(c).ljust(w[i]) for i, c in enumerate(r))
                for r in rows]
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default=None, choices=(None, "single",
                                                     "multi"))
    ap.add_argument("--variant", default=None)
    ap.add_argument("--md", default=None, help="write markdown table here")
    args = ap.parse_args(argv)

    recs = load_records(args.dir, args.mesh, args.variant)
    if not recs:
        print("no dry-run records found — run repro.launch.dryrun first")
        return 1
    print(table(recs))
    if args.md:
        os.makedirs(os.path.dirname(args.md) or ".", exist_ok=True)
        with open(args.md, "w") as f:
            f.write(table(recs, md=True) + "\n")
        print(f"\nmarkdown table → {args.md}")

    # summary: worst cells by each criterion (the §Perf cell-selection aid)
    singles = [r for r in recs if r["mesh"] == "single"
               and r["variant"]["name"] == "baseline"]
    if singles:
        worst_useful = min(singles, key=lambda r: terms(r)["useful_ratio"])
        most_coll = max(singles, key=lambda r: terms(r)["t_collective"])
        print("\n[selection] worst useful-compute ratio:",
              worst_useful["arch"], worst_useful["shape"],
              f"({terms(worst_useful)['useful_ratio']:.3f})")
        print("[selection] most collective-bound:",
              most_coll["arch"], most_coll["shape"],
              f"({fmt_s(terms(most_coll)['t_collective'])})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
