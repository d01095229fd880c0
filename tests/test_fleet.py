"""tdp.fleet: ensemble execution, the service driver, durability.

Covers the three layers:

* ``ProgramState`` — annotated pytree (names + optional ensemble axis),
  Mapping protocol, stack/member/unstack, validation messages that name
  the offending field and dimension.
* ``FleetProgram`` — ``compiled.vmap(batch)``: fleet trajectories are
  **bit-identical** to per-member runs.  The exact reference depends on
  the const story: programs with only static consts compare against
  plain single ``CompiledProgram`` runs; ``BatchedConst`` sweeps compare
  against batch-1 fleets (XLA constant-folds a *baked* scalar — e.g.
  ``/tau`` → multiply-by-reciprocal — so a static-const solo compile is
  the same trajectory only to ~1 ulp, while the served path is exact).
* ``FleetDriver`` — submit/poll/stream/drain, bucket reuse (one jit per
  sweep), warn-once per-member fallback for unbucketed grids, and
  kill-and-restore through the checkpoint store matching an
  uninterrupted run bit-for-bit.

The sharded case (vmap outside ``shard_map``) runs in a subprocess with
fake devices under the ``slow`` marker, like tests/test_distributed.py.
"""
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tdp
from repro.lb import programs as lbp
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim


# ---------------------------------------------------------------------------
# fixtures: a tiny 2-stage program with a sweepable const
# ---------------------------------------------------------------------------

@tdp.kernel(fields=[tdp.field(2)], out=2)
def _relax(x, tau=1.0, w=None):
    return x - (x - w[:, None]) / tau


@tdp.kernel(fields=[tdp.field(2), tdp.field(2)], out=2)
def _mix(x, y, eps=0.1):
    return x + eps * (y - x)


GRID = (6, 5)
W = tdp.TargetConst(np.array([0.25, 0.75], np.float32))


def make_prog(tau_const, name="demo"):
    return tdp.Program(name, [
        tdp.stage(_relax, ["a"], ["tmp"],
                  consts={"tau": tau_const, "w": W}),
        tdp.stage(_mix, ["a", "tmp"], ["a"], consts={"eps": 0.05}),
    ], fields=["a"])


def members(n, seed=0, grid=GRID):
    rng = np.random.default_rng(seed)
    return [{"a": jnp.asarray(
        rng.normal(size=(2,) + grid).astype(np.float32))}
        for _ in range(n)]


# ---------------------------------------------------------------------------
# ProgramState
# ---------------------------------------------------------------------------

class TestProgramState:
    def test_mapping_protocol(self):
        m = members(1)[0]
        s = tdp.ProgramState(m)
        assert list(s) == ["a"] and len(s) == 1 and s.fields == ("a",)
        assert s["a"] is m["a"] and dict(s)["a"] is m["a"]
        assert s.ensemble is None
        with pytest.raises(KeyError, match="no field 'b'.*fields: \\['a'\\]"):
            s["b"]

    def test_pytree_roundtrip_preserves_annotation(self):
        s = tdp.ProgramState.stack(members(3))
        leaves, treedef = jax.tree_util.tree_flatten(s)
        s2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert isinstance(s2, tdp.ProgramState)
        assert s2.ensemble == 3 and s2.fields == ("a",)
        # survives a jitted identity (annotation lives in aux data)
        s3 = jax.jit(lambda x: x)(s)
        assert isinstance(s3, tdp.ProgramState) and s3.ensemble == 3

    def test_stack_member_unstack(self):
        ms = members(4)
        s = tdp.ProgramState.stack(ms)
        assert s.ensemble == 4 and s["a"].shape == (4, 2) + GRID
        for i, m in enumerate(ms):
            np.testing.assert_array_equal(np.asarray(s.member(i)["a"]),
                                          np.asarray(m["a"]))
        parts = s.unstack()
        assert len(parts) == 4 and all(p.ensemble is None for p in parts)
        with pytest.raises(ValueError, match="already carries an ensemble"):
            tdp.ProgramState.stack([s, s])

    def test_replace(self):
        s = tdp.ProgramState(members(1)[0])
        z = jnp.zeros((2,) + GRID, np.float32)
        s2 = s.replace(a=z)
        assert s2["a"] is z and s["a"] is not z
        with pytest.raises(ValueError, match="unknown field"):
            s.replace(b=z)

    def test_validation_names_field_and_dim(self):
        bad_ncomp = {"a": jnp.zeros((3,) + GRID, np.float32)}
        with pytest.raises(ValueError,
                           match="field 'a'.*dim 0 \\(ncomp\\) is 3.*"
                                 "expected ncomp 2"):
            tdp.ProgramState(bad_ncomp).validate({"a": 2}, GRID)
        bad_grid = {"a": jnp.zeros((2, 6, 7), np.float32)}
        with pytest.raises(ValueError,
                           match="dim 2 \\(grid dim 1\\) is 7.*"
                                 "expected grid extent 5"):
            tdp.ProgramState(bad_grid).validate({"a": 2}, GRID)
        ens = tdp.ProgramState.stack(members(3))
        with pytest.raises(ValueError,
                           match="dim 0 \\(ensemble\\) is 3.*"
                                 "expected ensemble extent 4"):
            tdp.validate_field("a", ens["a"], ncomp=2, grid_shape=GRID,
                               ensemble=4)

    def test_compiled_program_accepts_program_state(self):
        cp = make_prog(tdp.TargetConst(np.float32(0.9))).compile(
            "xla", grid_shape=GRID)
        m = members(1)[0]
        out_dict = cp.run(dict(m), 3)
        out_ps = cp.run(tdp.ProgramState(m), 3)
        assert isinstance(out_dict, dict)
        assert isinstance(out_ps, tdp.ProgramState)
        np.testing.assert_array_equal(np.asarray(out_dict["a"]),
                                      np.asarray(out_ps["a"]))
        # ensembled state is rejected with a pointer to fleets
        with pytest.raises(ValueError, match="fleet|member"):
            cp.step(tdp.ProgramState.stack(members(2)))


# ---------------------------------------------------------------------------
# BatchedConst
# ---------------------------------------------------------------------------

class TestBatchedConst:
    def test_needs_leading_axis(self):
        with pytest.raises(ValueError, match="leading ensemble axis"):
            tdp.BatchedConst(3.0)
        bc = tdp.BatchedConst(np.arange(4.0))
        assert bc.batch == 4 and bc.member_shape() == ()

    def test_bare_launch_rejected(self):
        prog = make_prog(tdp.BatchedConst(np.ones(4, np.float32)))
        cp = prog.compile("xla", grid_shape=GRID)
        with pytest.raises(ValueError, match="vmap\\(batch\\)"):
            cp.run(members(1)[0], 1)
        with pytest.raises(ValueError, match="fleet"):
            tdp.launch(_relax, "xla",
                       members(1)[0]["a"].reshape(2, -1),
                       tau=tdp.BatchedConst(np.ones(4, np.float32)), w=W)

    def test_conflicting_sweeps_rejected(self):
        b1 = tdp.BatchedConst(np.arange(4.0))
        b2 = tdp.BatchedConst(np.arange(4.0) + 1)
        prog = tdp.Program("x", [
            tdp.stage(_relax, ["a"], ["tmp"], consts={"tau": b1, "w": W}),
            tdp.stage(_relax, ["tmp"], ["a"], consts={"tau": b2, "w": W}),
        ], fields=["a"])
        with pytest.raises(ValueError, match="two different BatchedConst"):
            prog.batched_consts()

    def test_batch_mismatch_names_const(self):
        prog = make_prog(tdp.BatchedConst(np.ones(4, np.float32)))
        cp = prog.compile("xla", grid_shape=GRID)
        with pytest.raises(ValueError, match="'tau' sweeps 4.*batch is 3"):
            cp.vmap(3)


# ---------------------------------------------------------------------------
# FleetProgram bit-identity
# ---------------------------------------------------------------------------

EXECUTORS = [
    tdp.Target("xla", vvl=32),
    tdp.Target("pallas", vvl=32, interpret=True),
]


class TestFleetBitIdentity:
    @pytest.mark.parametrize("target", EXECUTORS,
                             ids=["xla", "pallas_interpret"])
    @pytest.mark.parametrize("batch", [1, 4])
    def test_static_consts_match_single_runs(self, target, batch):
        prog = make_prog(tdp.TargetConst(np.float32(0.9)))
        cp = prog.compile(target, grid_shape=GRID)
        fleet = cp.vmap(batch)
        ms = members(batch)
        out = fleet.run(tdp.ProgramState.stack(ms), 5)
        assert isinstance(out, tdp.ProgramState) and out.ensemble == batch
        for i in range(batch):
            ref = cp.run(dict(ms[i]), 5)
            np.testing.assert_array_equal(np.asarray(out["a"][i]),
                                          np.asarray(ref["a"]))

    @pytest.mark.parametrize("target", EXECUTORS,
                             ids=["xla", "pallas_interpret"])
    def test_sweep_matches_batch1_fleets(self, target):
        B = 4
        taus = np.linspace(0.6, 1.4, B).astype(np.float32)
        fleet = make_prog(tdp.BatchedConst(taus)).compile(
            target, grid_shape=GRID).vmap(B)
        ms = members(B)
        out = fleet.run(tdp.ProgramState.stack(ms), 6)
        for i in range(B):
            f1 = make_prog(tdp.BatchedConst(taus[i:i + 1])).compile(
                target, grid_shape=GRID).vmap(1)
            ref = f1.run({"a": ms[i]["a"][None]}, 6)
            np.testing.assert_array_equal(np.asarray(out["a"][i]),
                                          np.asarray(ref["a"][0]))

    def test_step_equals_run_chunks(self):
        prog = make_prog(tdp.TargetConst(np.float32(0.8)))
        fleet = prog.compile("xla", grid_shape=GRID).vmap(2)
        s = tdp.ProgramState.stack(members(2))
        a = fleet.run(s, 4)
        b = s
        for _ in range(4):
            b = fleet.step(b)
        np.testing.assert_array_equal(np.asarray(a["a"]),
                                      np.asarray(b["a"]))

    def test_const_override_no_recompile(self):
        B = 3
        fleet = make_prog(tdp.BatchedConst(
            np.ones(B, np.float32))).compile(
            "xla", grid_shape=GRID).vmap(B)
        s = tdp.ProgramState.stack(members(B))
        fleet.run(s, 2)
        n_compiled = len(fleet._run_cache)
        fleet.run(s, 2, consts={"tau": np.full(B, 0.7, np.float32)})
        assert len(fleet._run_cache) == n_compiled   # same jit entry
        with pytest.raises(ValueError, match="binds no batched const"):
            fleet.run(s, 1, consts={"nope": np.ones(B)})
        with pytest.raises(ValueError, match="'tau'.*expected the fleet"):
            fleet.run(s, 1, consts={"tau": np.ones(B + 1, np.float32)})

    def test_state_validation_messages(self):
        fleet = make_prog(tdp.TargetConst(np.float32(0.9))).compile(
            "xla", grid_shape=GRID).vmap(2)
        with pytest.raises(ValueError, match="must carry an ensemble axis"):
            fleet.step(tdp.ProgramState(members(1)[0]))
        with pytest.raises(ValueError, match="ensemble extent 3 != fleet"):
            fleet.step(tdp.ProgramState.stack(members(3)))
        with pytest.raises(ValueError,
                           match="field 'a'.*dim 0 \\(ensemble\\)"):
            fleet.step({"a": jnp.zeros((3, 2) + GRID, np.float32)})

    def test_lb_fleet_matches_single_sims(self):
        """The acceptance case: a fleet of BinaryFluidSim trajectories
        is bit-identical to independent single runs."""
        sim = BinaryFluidSim(grid_shape=(8, 8, 8), backend="xla", vvl=64,
                             fused="two_launch")
        fused = sim.programs["fused"]
        B = 3
        states = []
        for seed in range(B):
            st = sim.init_spinodal(seed=seed)
            st = sim.programs["collide"].run({"f": st.f, "g": st.g}, 1)
            states.append(st)
        fleet = fused.vmap(B)
        out = fleet.run(tdp.ProgramState.stack(states), 4)
        for i in range(B):
            ref = fused.run(dict(states[i]), 4)
            for f in ("f", "g"):
                np.testing.assert_array_equal(np.asarray(out[f][i]),
                                              np.asarray(ref[f]))

    def test_lb_mobility_sweep(self):
        """Per-member tau_phi (mobility) sweep through BatchedConst."""
        B = 3
        tau_phis = np.array([0.8, 1.0, 1.2], np.float32)
        p = LBParams()

        def build(tau_phi_const):
            phys = p.as_kwargs()
            phys["tau_phi"] = tau_phi_const
            return lbp.unfused_step_program(
                lbp.collision_consts(np.float32, **phys))

        sim = BinaryFluidSim(grid_shape=(8, 8, 8), backend="xla", params=p)
        states = [sim.init_spinodal(seed=s) for s in range(B)]
        ms = [{"f": s.f, "g": s.g} for s in states]
        fleet = build(tdp.BatchedConst(tau_phis)).compile(
            "xla", grid_shape=(8, 8, 8)).vmap(B)
        out = fleet.run(tdp.ProgramState.stack(ms), 3)
        for i in range(B):
            f1 = build(tdp.BatchedConst(tau_phis[i:i + 1])).compile(
                "xla", grid_shape=(8, 8, 8)).vmap(1)
            ref = f1.run({k: v[None] for k, v in ms[i].items()}, 3)
            for f in ("f", "g"):
                np.testing.assert_array_equal(np.asarray(out[f][i]),
                                              np.asarray(ref[f][0]))


class TestFleetWindowed:
    def test_windowed_fleet_matches_windowed_singles(self):
        """Fleet bit-identity under the windowed (halo-extended)
        executor: fleet members == single runs of the same compile."""
        sim = BinaryFluidSim(grid_shape=(8, 8, 8), backend="xla",
                             fused="one_launch")
        st = sim.init_spinodal(seed=0)
        m0 = sim.programs["collide"].run({"f": st.f, "g": st.g}, 1)
        st1 = sim.init_spinodal(seed=1)
        m1 = sim.programs["collide"].run({"f": st1.f, "g": st1.g}, 1)
        consts = lbp.collision_consts(np.float32,
                                      **LBParams().as_kwargs())
        fusedp = lbp.fused_program("one_launch", consts)
        cp = fusedp.compile(tdp.Target("pallas_windowed", interpret=True),
                            grid_shape=(8, 8, 8))
        fleet = cp.vmap(2)
        out = fleet.run(tdp.ProgramState.stack([m0, m1]), 2)
        for i, m in enumerate([m0, m1]):
            ref = cp.run(dict(m), 2)
            for f in ("f", "g"):
                np.testing.assert_array_equal(np.asarray(out[f][i]),
                                              np.asarray(ref[f]))


# ---------------------------------------------------------------------------
# FleetDriver
# ---------------------------------------------------------------------------

class TestFleetDriver:
    def test_submit_poll_stream_drain_static(self):
        prog = make_prog(tdp.TargetConst(np.float32(0.9)))
        cp = prog.compile("xla", grid_shape=GRID)
        drv = tdp.FleetDriver("xla", batch=3)
        ms = members(4)
        ts = [drv.submit(prog, {"state": ms[i]}, 5 + i) for i in range(4)]
        marks = [s for s, _ in drv.stream(ts[0], every=2)]
        assert marks == [2, 4, 5]
        final = drv.drain()
        for i, t in enumerate(ts):
            ref = cp.run(dict(ms[i]), 5 + i)
            np.testing.assert_array_equal(np.asarray(final[t.id]["a"]),
                                          np.asarray(ref["a"]))
            p = drv.poll(t)
            assert p["done"] and p["step"] == 5 + i
        # 4 tickets > 3 slots still used exactly one bucket (one jit)
        assert len(drv._buckets) == 1

    def test_sweep_bucket_one_jit(self):
        prog = make_prog(tdp.TargetConst(np.float32(1.0)))
        B = 3
        taus = np.array([0.7, 1.0, 1.3], np.float32)
        drv = tdp.FleetDriver("xla", batch=B)
        ms = members(B)
        ts = [drv.submit(prog, {"state": ms[i], "consts": {"tau": taus[i]}},
                         6) for i in range(B)]
        final = drv.drain()
        assert len(drv._buckets) == 1
        for i, t in enumerate(ts):
            f1 = make_prog(tdp.BatchedConst(taus[i:i + 1])).compile(
                "xla", grid_shape=GRID).vmap(1)
            ref = f1.run({"a": ms[i]["a"][None]}, 6)
            np.testing.assert_array_equal(np.asarray(final[t.id]["a"]),
                                          np.asarray(ref["a"][0]))

    def test_fallback_warns_once_and_completes(self):
        prog = make_prog(tdp.TargetConst(np.float32(0.9)))
        drv = tdp.FleetDriver("xla", batch=2, grid_shapes=[GRID])
        odd = (4, 4)
        with warnings.catch_warnings(record=True) as wlist:
            warnings.simplefilter("always")
            t1 = drv.submit(prog, {"state": {
                "a": jnp.ones((2,) + odd, np.float32)}}, 3)
            t2 = drv.submit(prog, {"state": {
                "a": jnp.zeros((2,) + odd, np.float32)}}, 3)
        msgs = [x for x in wlist if "per-member" in str(x.message)]
        assert len(msgs) == 1 and "(4, 4)" in str(msgs[0].message)
        final = drv.drain()
        cp = prog.compile("xla", grid_shape=odd)
        ref = cp.run({"a": jnp.ones((2,) + odd, np.float32)}, 3)
        np.testing.assert_array_equal(np.asarray(final[t1.id]["a"]),
                                      np.asarray(ref["a"]))
        assert t1.bucket_id == "" and t2.done
        # bucketed grid still goes through the fleet path
        t3 = drv.submit(prog, {"state": members(1)[0]}, 2)
        drv.drain()
        assert t3.bucket_id != ""

    def test_background_thread(self):
        prog = make_prog(tdp.TargetConst(np.float32(0.9)))
        cp = prog.compile("xla", grid_shape=GRID)
        drv = tdp.FleetDriver("xla", batch=2)
        drv.start()
        try:
            m = members(1)[0]
            t = drv.submit(prog, {"state": m}, 12)
            final = drv.drain()
        finally:
            drv.stop()
        ref = cp.run(dict(m), 12)
        np.testing.assert_array_equal(np.asarray(final[t.id]["a"]),
                                      np.asarray(ref["a"]))

    def test_submit_validation(self):
        prog = make_prog(tdp.TargetConst(np.float32(0.9)))
        drv = tdp.FleetDriver("xla", batch=2)
        with pytest.raises(ValueError, match="one member per ticket"):
            drv.submit(prog, {"state": tdp.ProgramState.stack(members(2))},
                       3)
        with pytest.raises(ValueError, match="nsteps"):
            drv.submit(prog, {"state": members(1)[0]}, 0)
        with pytest.raises(ValueError, match="no stage binds const"):
            drv.submit(prog, {"state": members(1)[0],
                              "consts": {"zeta": 1.0}}, 3)


class TestFleetDurability:
    def test_kill_and_resume_matches_uninterrupted(self, tmp_path):
        prog = make_prog(tdp.TargetConst(np.float32(1.0)))
        taus = np.array([0.7, 1.1], np.float32)
        ms = members(2)
        ck = str(tmp_path / "ck")

        drv = tdp.FleetDriver("xla", batch=2, checkpoint_dir=ck)
        tA = drv.submit(prog, {"state": ms[0], "consts": {"tau": taus[0]},
                               "rng": jax.random.PRNGKey(3)}, 9)
        tB = drv.submit(prog, {"state": ms[1], "consts": {"tau": taus[1]}},
                        4)
        drv.pump(3)                      # mid-flight: A at 3/9, B at 3/4
        drv.checkpoint()
        del drv                          # "kill"

        drv2 = tdp.FleetDriver.restore(ck, {"demo": prog})
        rA, rB = drv2._tickets[tA.id], drv2._tickets[tB.id]
        assert rA.step == 3 and not rA.done
        assert rB.step == 3 and not rB.done
        assert rA.rng is not None
        np.testing.assert_array_equal(np.asarray(rA.rng),
                                      np.asarray(jax.random.PRNGKey(3)))
        final = drv2.drain()
        assert drv2._tickets[tA.id].step == 9

        # uninterrupted reference driver
        ref = tdp.FleetDriver("xla", batch=2)
        uA = ref.submit(prog, {"state": ms[0], "consts": {"tau": taus[0]}},
                        9)
        uB = ref.submit(prog, {"state": ms[1], "consts": {"tau": taus[1]}},
                        4)
        rfinal = ref.drain()
        np.testing.assert_array_equal(np.asarray(final[tA.id]["a"]),
                                      np.asarray(rfinal[uA.id]["a"]))
        np.testing.assert_array_equal(np.asarray(final[tB.id]["a"]),
                                      np.asarray(rfinal[uB.id]["a"]))

    def test_completed_tickets_restore_completed(self, tmp_path):
        prog = make_prog(tdp.TargetConst(np.float32(1.0)))
        ck = str(tmp_path / "ck")
        drv = tdp.FleetDriver("xla", batch=2, checkpoint_dir=ck)
        t = drv.submit(prog, {"state": members(1)[0]}, 2)
        drv.drain()
        drv.checkpoint()
        drv2 = tdp.FleetDriver.restore(ck, prog)
        assert drv2._tickets[t.id].done
        assert drv2.drain()[t.id]["a"].shape == (2,) + GRID

    def test_periodic_checkpoint_cadence(self, tmp_path):
        from repro.checkpoint.store import latest_step
        prog = make_prog(tdp.TargetConst(np.float32(1.0)))
        ck = str(tmp_path / "ck")
        drv = tdp.FleetDriver("xla", batch=2, checkpoint_dir=ck,
                              checkpoint_every=2)
        drv.submit(prog, {"state": members(1)[0]}, 5)
        drv.drain()
        assert latest_step(ck) is not None    # cadence fired mid-drain


# ---------------------------------------------------------------------------
# sharded fleet (vmap outside shard_map), in a fake-device subprocess
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
class TestShardedFleet:
    def test_slab_sharded_fleet_matches_single_device(self):
        run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
assert len(jax.devices()) == 8
from repro import tdp
from repro.lb import programs as lbp
from repro.lb.params import LBParams

consts = lbp.collision_consts(np.float32, **LBParams().as_kwargs())
prog = lbp.fused_program("two_launch", consts)
grid = (8, 8, 8)
mesh = Mesh(np.array(jax.devices()[:2]), ("x",))

rng = np.random.default_rng(0)
B = 3
ms = [{f: jnp.asarray(rng.normal(size=(19,) + grid).astype(np.float32))
       for f in ("f", "g")} for _ in range(B)]
state = tdp.ProgramState.stack(ms)

local = prog.compile(tdp.Target("xla", vvl=64),
                     grid_shape=grid).vmap(B)
shard = prog.compile(tdp.Target("xla", vvl=64, mesh=mesh,
                                shard_axis="x"),
                     grid_shape=grid).vmap(B)
a = local.run(state, 3)
b = shard.run(state, 3)
tdp.check_fields(a, b)
print("sharded-fleet-ok")
""")
