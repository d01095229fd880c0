"""The program's own names in a chip trace, and what the benchmark reads
of a trace that carries them.

`data/<cell>.scoped.*` were recorded on the chip by `bench/calibrate.py
--record-trace` (three-step calls) after the program put its work under
`tdp.*` scopes, modules and host spans; `data/<cell>.short.*` before.
`bench/lib/xplane.py` reads each op's scope path (`tf_op`) from the
profiler's file, which `jax.profiler.ProfileData` does not expose.
"""
import gzip
import json
import os

import pytest

from bench_helpers import DATA, ROOT, load_json, recorded

from bench.lib import harness, trace as tr, xplane

CELLS = ["spinodal128.stats100", "pencil2x2.stats100"]
#: The stages of each cell's fused step, and the program names.
STAGES = {"spinodal128.stats100": ("fused",), "pencil2x2.stats100": ("phi_stream", "fused_two")}
FUSED = {"spinodal128.stats100": "lb_fused_one", "pencil2x2.stats100": "lb_fused_two"}
STATS = ("mass", "phi_total", "phi_min", "phi_max", "phi_var", "rho_min", "nan")


def xplane_file(tmp_path, name):
    raw = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, name), "rb") as fh:
        raw.write_bytes(fh.read())
    return str(raw)


def is_tpu(plane):
    return plane.startswith("/device:TPU:") and plane[len("/device:TPU:"):].isdigit()


def scoped_ops(path):
    """{device: [(op, scope path, start_ns, dur_ns)]} of the XLA Ops lines."""
    named = tr.from_xplane(path)
    with open(path, "rb") as fh:
        planes = xplane.op_scopes(fh.read(), tr.OPS_LINE, is_tpu)
    out = {}
    for dev, evs in planes:
        paths = tr.leaves(evs)
        ops = named.devices[dev]
        assert [(s, d) for _, s, d in paths] == [(s, d) for _, s, d in ops], dev
        out[dev] = [(n, p, s, d) for (n, s, d), (p, _, _) in zip(ops, paths)]
    return out


def event_names(path, plane_prefix, line_name=None):
    """Names of the events on the planes whose name starts with
    `plane_prefix` (on their line `line_name`, if given)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    return [e.name for plane in pd.planes if plane.name.startswith(plane_prefix)
            for line in plane.lines if line_name in (None, line.name) for e in line.events]


def scoped_recording(cell):
    """The `Context` of the scoped window recorded for `cell`."""
    with open(os.path.join(DATA, f"{cell}.scoped.ctx.json")) as fh:
        c = json.load(fh)
    t = tr.Trace.from_json(os.path.join(DATA, f"{cell}.scoped.trace.json.gz"))
    return harness.Context(t, t.window(), c["calls"], c["steps"], c["kernel_steps"],
                           c["chips"], c["sites_per_chip"], c["device_kind"])


def test_op_scopes_of_a_trace_without_the_programs_scopes(tmp_path):
    """The first recorded file: every leaf op gets the path it was lowered
    under, and the output relayout XLA adds gets none."""
    ops = scoped_ops(xplane_file(tmp_path, "spinodal128.stats100.short.xplane.pb.gz"))
    paths = {n: p for n, p, _, _ in ops["/device:TPU:0"]}
    assert paths["tdp_windowed_fused_site_kernel_p1_soa.3"] == (
        "jit(many)/while/body/closed_call/jit(run)/tdp_windowed_fused_site_kernel_p1_soa/"
        "pallas_call")
    assert paths["pad_maximum_fusion.21"].endswith("jit(run)/jit(_pad)/concatenate")
    assert paths["copy.4"] == "" and paths["slice.80"] == ""
    assert not any("tdp." in p for p in paths.values())


@pytest.mark.parametrize("cell", CELLS)
def test_program_scopes_reach_the_chip_trace(cell, tmp_path):
    path = xplane_file(tmp_path, f"{cell}.scoped.xplane.pb.gz")
    ops = scoped_ops(path)
    assert len(ops) == load_json("tests", "bench", "data", f"{cell}.scoped.ctx.json")["chips"]
    for dev_ops in ops.values():
        by_scope = {}
        for n, p, _, _ in dev_ops:
            for scope in p.split("/"):
                by_scope.setdefault(scope, set()).add(n)
        for stage in STAGES[cell]:
            assert any(tr.is_kernel(n) for n in by_scope[f"tdp.stage.{stage}"]), stage
        assert by_scope["tdp.halo_extend"] and not any(
            tr.is_kernel(n) for n in by_scope["tdp.halo_extend"])
        assert any(tr.is_kernel(n) for n in by_scope[f"jit(tdp_{FUSED[cell]}_run)"])
        if cell.startswith("pencil"):
            assert any(tr.is_exchange(n) for n in by_scope["tdp.exchange"])
        else:
            assert "tdp.exchange" not in by_scope
    modules = {m.split("(")[0] for m in event_names(path, "/device:TPU:", "XLA Modules")}
    assert {f"jit_tdp_{FUSED[cell]}_run", "jit_tdp_lb_collide_step",
            "jit_tdp_lb_stream_step"} <= modules
    host = set(event_names(path, "/host:"))
    assert {"tdp.sim.run", "tdp.sim.observables", f"tdp.{FUSED[cell]}.run",
            "tdp.lb_collide.step", "tdp.lb_stream.step"} <= host
    assert {f"tdp.observables.{s}" for s in STATS} <= host


#: How far a metric of the same program may read from one short window to
#: another (relative): device op times repeat to 0.1% (the two windows
#: differ by 0.06% in `xla_ops_ms_per_step`); what the host's timing of two
#: three-step calls sets (statistics, idle, the window), and the 0.03 ms of
#: exposed exchange, by up to 4%.
SAME = {"xla_ops_ms_per_step": 0.01, "kernel_roofline_share": 0.01, "exchange_ms_per_step": 0.01,
        "exchange_exposed_ms_per_step": 0.1, "observables_ms_per_call": 0.1,
        "device_idle_share": 0.1, "step_mfu": 0.1}


@pytest.mark.parametrize("cell", CELLS)
def test_existing_metrics_read_a_scoped_trace_as_before(cell):
    """The program's scopes and spans move no number the benchmark reads:
    each metric of the cell reads the scoped window as it read the first."""
    bench = load_json("BENCHMARK.json")
    before = harness.per_layer(ROOT, bench, {"name": cell}, recorded(cell))
    after = harness.per_layer(ROOT, bench, {"name": cell}, scoped_recording(cell))
    assert set(after) == set(before) <= set(SAME)
    for name, got in after.items():
        assert got["value"] == pytest.approx(before[name]["value"], rel=SAME[name]), name
