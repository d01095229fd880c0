"""The pencil's run on four devices, and the same run with the exchange
between chips left out, which has to come out not correct.

The four devices are the CPU's, faked by XLA_FLAGS in a child process
(the test process keeps one device); the whole run but the look for a
chip is `harness.run_cell`, at a small size.
"""
import json
import os
import subprocess
import sys

from bench_helpers import ROOT, small_config

CHILD = r"""
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import importlib
import jax
from bench.lib import harness
program = importlib.import_module("repro.core.program")

results = {{}}
for fault in ("none", "exchange_left_out"):
    if fault == "exchange_left_out":
        # every shard takes its own boundary planes as ghosts
        program._exchange_dim = lambda arr, axis_name, width, dim: program.exchange_ghosts(
            arr, dim, width, 1, lambda x, pairs: x)
    res = harness.run_cell({path!r}, "small_pencil.stats10", 2 ** 33 + 1, 0.5, False,
                           time.perf_counter(), log=lambda line: None)
    results[fault] = {{"correct": res["correct"], "check": res["check"],
                      "count": len(jax.devices())}}
print(json.dumps(results))
"""


def test_exchange_left_out_is_not_correct(scratch_root):
    scratch_root.add("configs", "small_pencil", small_config(
        "small_pencil", base="ludwig_spinodal_pencil_2x2", grid=[16, 16, 16], executor="xla"))
    scratch_root.add("traffic", "stats10", {"name": "stats10", "steps_per_call": 10,
                                            "donate": True})
    scratch_root.add_cell({"name": "small_pencil.stats10", "config": "small_pencil",
                           "traffic": "stats10", "chips": 4, "why": "test"})
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    code = CHILD.format(root=ROOT, src=os.path.join(ROOT, "src"), path=scratch_root.path)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    sound, broken = res["none"], res["exchange_left_out"]
    assert sound["count"] == 4
    assert sound["correct"], sound["check"]
    assert sound["check"]["devices_missing"]["value"] == 0
    assert not broken["correct"]
    c = broken["check"]["max_abs_dg"]
    assert not c["value"] <= c["limit"], broken["check"]
