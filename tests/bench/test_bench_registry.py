"""Cells, traffic mixes and per-layer metrics are found by name from files
of their own, so that a later change adds them as new files only."""
import os
import re
import time

from bench_helpers import ROOT, ctx_of, hand_trace, load_json, small_config

from bench.lib import harness, registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

NEW_METRIC = '''"""Calls completed in the traced window."""


def read(ctx):
    return float(ctx.calls)
'''


def test_new_config_traffic_and_metric_are_found_by_name(scratch_root):
    before = {p: open(os.path.join(scratch_root.path, "bench", p), "rb").read()
              for p in ("configs/ludwig_spinodal_128.json", "traffic/stats100.json",
                        "metrics/step_mfu.py", "lib/harness.py")}
    scratch_root.add("configs", "tiny", small_config("tiny", grid=[8, 8, 8], executor="xla"))
    scratch_root.add("traffic", "stats5", {"name": "stats5", "steps_per_call": 5,
                                           "donate": True})
    scratch_root.add("metrics", "calls_in_window", NEW_METRIC)
    scratch_root.add_cell({"name": "tiny.stats5", "config": "tiny", "traffic": "stats5",
                           "chips": 1, "why": "test"})
    scratch_root.add_metric({"name": "calls_in_window", "unit": "calls", "better": "higher",
                             "source": "host_clock", "layer": "simulation driver (lb/sim.py)",
                             "moves": "mlups_per_chip", "workloads": ["tiny.stats5"]})

    res = harness.run_cell(scratch_root.path, "tiny.stats5", seed=12, seconds=0.2,
                           trace=False, t_start=time.perf_counter(), log=lambda line: None)
    assert res["correct"], res["check"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"mlups_per_chip", "setup_s"}
    assert res["metrics"]["mlups_per_chip"]["value"] > 0

    bench = registry.load_benchmark(scratch_root.path)
    got = harness.per_layer(scratch_root.path, bench, {"name": "tiny.stats5"},
                            ctx_of(hand_trace(), calls=3))
    assert got == {"calls_in_window": {"value": 3.0, "unit": "calls"}}
    for p, content in before.items():
        assert open(os.path.join(scratch_root.path, "bench", p), "rb").read() == content


def test_benchmark_json_names_existing_files():
    bench = load_json("BENCHMARK.json")
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    for c in bench["configs"]:
        cfg = registry.load_config(ROOT, c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert all(key in cfg for key in c["reduced"])
        for key in ("source", "deployment", "assumed", "limits", "reference"):
            assert cfg[key], key
        ref = registry.load_reference(cfg["reference"])
        assert all(hasattr(ref, a) for a in ("WEIGHTS", "run", "observables"))
        assert all(v is not None for v in cfg["limits"].values())
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert {"mlups_per_chip", "setup_s"} <= e2e
    for w in bench["workloads"]:
        registry.load_config(ROOT, w["config"])
        registry.load_traffic(ROOT, w["traffic"])
        assert any(w["name"] in m.get("workloads", [w["name"]]) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert hasattr(registry.load_metric(ROOT, m["name"]), "read")
    names = ([c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
