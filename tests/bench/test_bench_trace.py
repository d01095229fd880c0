"""The reduction from a device trace to the per-layer metrics.

Hand-built traces pin the interval arithmetic (union, exposed exchange,
idle gaps and their labels); a short trace recorded on one v5e chip
(`data/`, three-step calls of `spinodal128.stats100`, made by
`bench/calibrate.py --record-trace`) pins the reading of a real one.
"""
import gzip
import os

import pytest

from bench_helpers import DATA, ROOT, ctx_of, hand_trace, load_json, recorded

from bench.lib import harness, registry, trace as tr


def read(name, ctx):
    return registry.load_metric(ROOT, name).read(ctx)


def test_union_and_length():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.length([(0, 10), (5, 15), (20, 21)]) == 16
    assert tr.clip([(0, 10), (15, 30), (40, 50)], (5, 20)) == [(5, 10), (15, 20)]


def test_exposed_counts_only_time_with_nothing_else_running():
    # exchange 0-10 and 20-30; other ops 2-4, 8-22, 25-26
    ex = [(0, 10), (20, 30)]
    others = [(2, 4), (8, 22), (25, 26)]
    # covered: 2-4 (2), 8-10 (2), 20-22 (2), 25-26 (1) -> exposed 20 - 7
    assert tr.exposed(ex, others) == 13
    assert tr.exposed(ex, []) == 20
    assert tr.exposed(ex, [(-5, 40)]) == 0


def test_gaps_and_labels():
    spans = [("bench.window", 0, 100), ("bench.run", 0, 10), ("bench.block", 10, 60),
             ("bench.observables", 70, 30)]
    g = tr.gaps([(5, 20), (15, 60), (75, 80)], (0, 100))
    assert g == [(0, 5), (60, 75), (80, 100)]
    assert [tr.innermost_span(spans, (s + e) // 2) for s, e in g] == [
        "bench.run", "bench.block", "bench.observables"]
    assert tr.innermost_span(spans, 200) == "none"


def test_metrics_on_a_hand_built_trace():
    ctx = ctx_of(hand_trace(), steps=10, kernel_steps=10, chips=2)
    assert read("exchange_ms_per_step", ctx) == pytest.approx(3.0)            # 30 ms / 10
    # device 0: 20 ms exposed; device 1: 30-60 minus 30-40 and 50-60 -> 10 ms
    assert read("exchange_exposed_ms_per_step", ctx) == pytest.approx(2.0)
    assert read("xla_ops_ms_per_step", ctx) == pytest.approx(1.5)             # (10 + 20) / 2 / 10
    # busy (ops, not ops in flight): device 0 80 ms, device 1 60 ms of 100
    assert read("device_idle_share", ctx) == pytest.approx(30.0)
    assert read("observables_ms_per_call", ctx) == pytest.approx(10.0)
    least = 128 ** 3 * 304 / 819e9
    assert read("kernel_roofline_share", ctx) == pytest.approx(100 * least * 10 / 0.045)
    assert read("step_mfu", ctx) == pytest.approx(100 * least * 10 / 0.1)


def test_a_metric_with_nothing_to_read_is_left_out():
    t = hand_trace()
    t.devices = {k: [e for e in v if not tr.is_exchange(e[0]) and not tr.is_kernel(e[0])]
                 for k, v in t.devices.items()}
    t.async_ops = {}
    ctx = ctx_of(t)
    assert read("exchange_ms_per_step", ctx) is None
    assert read("exchange_exposed_ms_per_step", ctx) is None
    assert read("kernel_roofline_share", ctx) is None
    bench = load_json("BENCHMARK.json")
    got = harness.per_layer(ROOT, bench, {"name": "pencil2x2.stats100"}, ctx)
    assert "exchange_ms_per_step" not in got and "xla_ops_ms_per_step" in got


def test_breakdown_lists_top_ops_and_labelled_gaps():
    b = harness.breakdown(ctx_of(hand_trace(), chips=2))
    assert b["device_ops"][0] == ["tdp_windowed_fused_p1_soa", pytest.approx(0.045)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0] == ["bench.block", pytest.approx(0.03)]          # device 1, 70-100 ms
    assert ["bench.block", pytest.approx(0.01)] in b["idle_gaps"]             # device 1, 40-50 ms
    assert {g[0] for g in b["idle_gaps"]} <= {"bench.block", "bench.observables"}


@pytest.mark.parametrize("cell", ["spinodal128.stats100", "pencil2x2.stats100"])
def test_recorded_chip_trace(cell):
    ctx = recorded(cell)
    bench = load_json("BENCHMARK.json")
    got = harness.per_layer(ROOT, bench, {"name": cell}, ctx)
    want = {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", [])}
    assert set(got) == want
    for name in ("kernel_roofline_share", "step_mfu", "device_idle_share"):
        assert 0 < got[name]["value"] < 100, (name, got[name])
    assert got["step_mfu"]["value"] < got["kernel_roofline_share"]["value"]
    if "exchange_ms_per_step" in want:
        assert 0 <= got["exchange_exposed_ms_per_step"]["value"] <= got[
            "exchange_ms_per_step"]["value"]
        assert len(ctx.trace.devices) == ctx.chips == 4
    b = harness.breakdown(ctx)
    assert any(tr.is_kernel(name) for name, _ in b["device_ops"])


def test_reading_the_profilers_file(tmp_path):
    """The recorded xplane file reduces to the committed events: leaf ops
    only (the scan's `while` is dropped), names cut at ` = `, the spans."""
    raw = tmp_path / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "spinodal128.stats100.short.xplane.pb.gz"), "rb") as fh:
        raw.write_bytes(fh.read())
    got = tr.from_xplane(str(raw))
    want = recorded("spinodal128.stats100").trace
    assert got.devices == want.devices and got.spans == want.spans
    ops = got.devices["/device:TPU:0"]
    assert not any(n.startswith("while") for n, _, _ in ops)
    assert any(n.startswith("tdp_windowed_fused_site_kernel") for n, _, _ in ops)
    assert all(" " not in n and not n.startswith("%") for n, _, _ in ops)
    assert {n for n, _, _ in got.spans} >= {"bench.window", "bench.run", "bench.block",
                                           "bench.observables"}
