"""``tdp.costmodel`` — the analytical performance model.

What must hold:

* **monotonicity** — :func:`roofline_seconds` is non-decreasing in every
  one of flops / hbm_bytes / vmem_bytes / comm_bytes (seeded random
  sweeps, no wall clock anywhere);
* **bottleneck attribution** — compute vs hbm vs vmem-spill vs comm
  picked by the dominant term, spill only above the VMEM capacity;
* **profile cache** — round-trips through ``machine-<device>.json``,
  corrupt/mismatched files are misses (never errors), interpret
  profiles live under a separate key and can never answer for compiled
  plans (the honest-profile rule);
* **FLOP counting** — :func:`kernel_flops` is exact on a hand-countable
  kernel (jaxpr-traced, not estimated);
* **predict dispatch** — LaunchPlan / Program / ProgramPlan /
  CompiledProgram all answer, ``source="hlo"`` only for compiled
  programs, per-stage rows sum to the total;
* **compat shims** — ``repro.launch.hlo_analysis`` re-exports the
  absorbed walker; ``dryrun_record_terms`` matches the roofline CLI.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro import tdp
from repro.core import costmodel as cm
from repro.core.costmodel import (
    CostEstimate,
    MachineProfile,
    kernel_flops,
    load_profile,
    machine_profile,
    predict,
    profile_path,
    roofline_seconds,
    store_profile,
)
from repro.lb import programs as lbp
from repro.lb.params import LBParams

GRID = (8, 8, 8)
PARAMS = LBParams(A=0.125, B=0.125, kappa=0.02)
WT = tdp.Target("pallas_windowed", interpret=True)

#: fixed rates so every expectation below is hand-computable
PROF = MachineProfile(device="test", peak_flops=1e9, hbm_bw=1e8,
                     vmem_bytes=1024, link_bw=1e7, source="test")
IPROF = dataclasses.replace(PROF, interpret=True)


def fused_prog(mode="two_launch"):
    return lbp.fused_program(
        mode, lbp.collision_consts(**PARAMS.as_kwargs()))


def lb_state(grid=GRID, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    f = jnp.asarray(0.05 * rng.normal(size=(19,) + grid) + 1 / 19.,
                    jnp.float32)
    g = jnp.asarray(0.05 * rng.normal(size=(19,) + grid), jnp.float32)
    return {"f": f, "g": g}


class TestRoofline:
    """The pure arithmetic core — seeded sweeps, no measurement."""

    def test_hand_computed_terms(self):
        est = roofline_seconds(1e9, 1e8, profile=PROF)
        assert est.t_compute == pytest.approx(1.0)
        assert est.t_hbm == pytest.approx(1.0)
        assert est.seconds == pytest.approx(1.0)
        assert est.bottleneck == "compute"    # ties go to compute

    def test_bottleneck_attribution(self):
        assert roofline_seconds(1e10, 1e6, profile=PROF).bottleneck \
            == "compute"
        assert roofline_seconds(1e3, 1e8, profile=PROF).bottleneck == "hbm"
        assert roofline_seconds(
            1e3, 1e8, vmem_bytes=4096, profile=PROF).bottleneck \
            == "vmem-spill"
        assert roofline_seconds(
            1e3, 1e3, comm_bytes=1e8, profile=PROF).bottleneck == "comm"

    def test_vmem_spill_derates_hbm(self):
        base = roofline_seconds(0, 1e8, profile=PROF)
        spilled = roofline_seconds(0, 1e8, vmem_bytes=2048, profile=PROF)
        assert spilled.t_hbm == pytest.approx(2 * base.t_hbm)

    @pytest.mark.parametrize("axis", ["flops", "hbm_bytes", "vmem_bytes",
                                      "comm_bytes"])
    def test_monotone_in_each_input(self, axis):
        rng = np.random.default_rng(hash(axis) % 2**32)
        for _ in range(50):
            kw = {"flops": float(rng.uniform(0, 1e12)),
                  "hbm_bytes": float(rng.uniform(0, 1e10)),
                  "vmem_bytes": float(rng.uniform(0, 1e7)),
                  "comm_bytes": float(rng.uniform(0, 1e9))}
            lo = dict(kw)
            hi = dict(kw)
            hi[axis] = kw[axis] * (1 + float(rng.uniform(0, 3)))
            f_lo, f_hi = lo.pop("flops"), hi.pop("flops")
            h_lo, h_hi = lo.pop("hbm_bytes"), hi.pop("hbm_bytes")
            s_lo = roofline_seconds(f_lo, h_lo, profile=PROF, **lo)
            s_hi = roofline_seconds(f_hi, h_hi, profile=PROF, **hi)
            assert s_hi.seconds >= s_lo.seconds

    def test_estimate_serializes(self):
        est = roofline_seconds(1e6, 1e6, profile=PROF)
        d = est.as_dict()
        assert d["bottleneck"] == est.bottleneck
        assert d["seconds"] == est.seconds
        json.dumps(d)    # JSON-safe throughout


class TestMachineProfile:
    """The calibrated-rates cache under results/tuning/."""

    def test_cache_round_trip(self, tmp_path):
        p = store_profile(str(tmp_path), PROF)
        assert p == profile_path(str(tmp_path), "test", False)
        back = load_profile(str(tmp_path), "test", False)
        assert back is not None
        assert back.peak_flops == PROF.peak_flops
        assert back.hbm_bw == PROF.hbm_bw
        assert back.source == "cached"

    def test_corrupt_file_is_a_miss(self, tmp_path):
        path = profile_path(str(tmp_path), "test", False)
        with open(path, "w") as fh:
            fh.write("{not json")
        assert load_profile(str(tmp_path), "test", False) is None

    def test_device_mismatch_is_a_miss(self, tmp_path):
        store_profile(str(tmp_path), PROF)
        path = profile_path(str(tmp_path), "test", False)
        d = json.load(open(path))
        d["device"] = "other"
        json.dump(d, open(path, "w"))
        assert load_profile(str(tmp_path), "test", False) is None

    def test_interpret_profiles_are_keyed_separately(self, tmp_path):
        store_profile(str(tmp_path), PROF)
        store_profile(str(tmp_path), IPROF)
        assert profile_path(str(tmp_path), "test", True) \
            != profile_path(str(tmp_path), "test", False)
        assert load_profile(str(tmp_path), "test", True).interpret
        assert not load_profile(str(tmp_path), "test", False).interpret

    def test_machine_profile_hits_disk_cache(self, tmp_path):
        dev = "fake-dev"
        prof = dataclasses.replace(PROF, device=dev)
        store_profile(str(tmp_path), prof)
        got = machine_profile(dev, cache_dir=str(tmp_path))
        assert got.source == "cached"
        assert got.peak_flops == PROF.peak_flops
        # the memo answers the second call even if the file vanishes
        os.remove(profile_path(str(tmp_path), dev, False))
        assert machine_profile(dev, cache_dir=str(tmp_path)) is got

    def test_default_table_without_calibration(self, tmp_path):
        got = machine_profile("cpu:cpu", cache_dir=str(tmp_path),
                              calibrate_if_missing=False)
        assert got.source == "default"
        assert got.peak_flops == cm.DEVICE_PEAKS["cpu"]["peak_flops"]
        assert not os.listdir(tmp_path)    # store=False never writes
        # a device kind with no table row is an error, never a default
        with pytest.raises(KeyError, match="nosuch-dev"):
            machine_profile("tpu:nosuch-dev", cache_dir=str(tmp_path),
                            calibrate_if_missing=False)

    def test_vmem_limit_follows_device_kind(self, monkeypatch):
        """The VMEM budget a compiled launch is given comes from its
        device kind's table row; interpret mode keeps a fixed cap, and a
        kind with no row raises."""
        assert cm.vmem_limit_bytes(interpret=True) == cm.INTERPRET_VMEM_LIMIT
        assert cm.vmem_limit_bytes() == cm.DEVICE_PEAKS["cpu"]["vmem_limit"]
        monkeypatch.setattr(cm, "device_kind", lambda: "TPU v5 lite")
        assert cm.vmem_limit_bytes() == 100 * 2 ** 20
        monkeypatch.setattr(cm, "device_kind", lambda: "TPU v99")
        with pytest.raises(KeyError, match="TPU v99"):
            cm.vmem_limit_bytes()
        assert cm.vmem_limit_bytes(interpret=True) == cm.INTERPRET_VMEM_LIMIT

    def test_honest_profile_rule(self):
        prog = fused_prog("one_launch")
        plan = prog.plan(WT, grid_shape=GRID)
        with pytest.raises(ValueError, match="interpret"):
            predict(plan, profile=PROF)        # compiled rates, interpret plan
        est = predict(plan, profile=IPROF)     # matching flag answers
        assert est.seconds > 0


class TestKernelFlops:
    """jaxpr-traced FLOPs — exact on a hand-countable kernel."""

    def test_pointwise_exact(self):
        @tdp.kernel(fields=[tdp.field(2)], out=2)
        def double2(x):
            return x + x                       # 1 add × 2 comp × nsites

        plan = tdp.launch_plan(double2, tdp.Target("xla", vvl=64),
                               lattice=tdp.Lattice(GRID))
        nsites = int(np.prod(GRID))
        assert kernel_flops(plan) == pytest.approx(2 * nsites)

    def test_scales_with_ops(self):
        @tdp.kernel(fields=[tdp.field(1)], out=1)
        def three_ops(x):
            return (x + x) * x + x             # add + mul + add

        plan = tdp.launch_plan(three_ops, tdp.Target("xla", vvl=64),
                               lattice=tdp.Lattice(GRID))
        assert kernel_flops(plan) == pytest.approx(3 * np.prod(GRID))


class TestPredict:
    """Dispatch over the four subject kinds + the two backends."""

    def test_launch_plan(self):
        @tdp.kernel(fields=[tdp.field(2)], out=2)
        def double2(x):
            return x + x

        plan = tdp.launch_plan(double2, tdp.Target("xla", vvl=64),
                               lattice=tdp.Lattice(GRID))
        est = predict(plan, profile=PROF)
        assert isinstance(est, CostEstimate)
        assert est.seconds > 0
        assert len(est.per_stage) == 1
        assert est.source == "analytic"

    def test_program_and_plan_agree(self):
        prog = fused_prog("two_launch")
        est_prog = predict(prog, WT, IPROF, grid_shape=GRID)
        est_plan = predict(prog.plan(WT, grid_shape=GRID), profile=IPROF)
        assert est_prog.seconds == pytest.approx(est_plan.seconds)
        assert [r["stage"] for r in est_prog.per_stage] \
            == ["phi_stream", "fused_two"]
        # stage rows + comm sum to the total
        assert est_prog.seconds == pytest.approx(
            sum(r["seconds"] for r in est_prog.per_stage)
            + est_prog.t_comm)

    def test_compiled_program(self):
        exe = fused_prog("two_launch").compile(
            tdp.Target("xla"), grid_shape=GRID)
        est = predict(exe, profile=PROF)
        assert est.flops > 0
        assert est.hbm_bytes > 0

    @pytest.mark.slow
    def test_hlo_backend(self):
        exe = fused_prog("two_launch").compile(
            tdp.Target("xla"), grid_shape=GRID)
        est = predict(exe, profile=PROF, source="hlo")
        assert est.source == "hlo"
        assert est.flops > 0
        assert est.hbm_bytes > 0
        assert est.per_stage[0]["stage"] == "<step>"

    def test_hlo_needs_compiled_program(self):
        with pytest.raises(ValueError, match="hlo"):
            predict(fused_prog("one_launch"), WT, IPROF,
                    grid_shape=GRID, source="hlo")

    def test_bad_source_rejected(self):
        with pytest.raises(ValueError, match="source"):
            predict(fused_prog("one_launch"), WT, IPROF,
                    grid_shape=GRID, source="vibes")

    def test_comm_term_from_override(self):
        prog = fused_prog("one_launch")
        plan = prog.plan(WT, grid_shape=GRID)
        quiet = predict(plan, profile=IPROF)
        chatty = predict(plan, profile=IPROF,
                         comm={"exchanged_bytes_per_step": 10**9})
        assert chatty.seconds > quiet.seconds
        assert chatty.comm_bytes == 10**9


class TestAbsorbedAnalysis:
    """The HLO walker + dry-run terms moved here; shims must hold."""

    def test_hlo_analysis_shim(self):
        from repro.launch import hlo_analysis as shim
        assert shim.analyze is cm.analyze
        assert shim.parse_module is cm.parse_module
        assert shim._multipliers is cm._multipliers

    def test_collective_bytes_empty(self):
        got = cm.collective_bytes("")
        assert got["total_bytes"] == 0
        assert all(v == 0 for v in got["bytes"].values())

    def test_dryrun_record_terms(self):
        rec = {"hlo_analysis": {"flops": 1e15, "traffic_bytes": 1e12,
                                "wire_bytes_ici": 1e10,
                                "wire_bytes_dcn": 0},
               "n_devices": 4, "model_flops": 2e15,
               "memory_analysis": {"argument_size_in_bytes": 2 ** 30,
                                   "temp_size_in_bytes": 2 ** 30}}
        t = cm.dryrun_record_terms(rec)
        tpu = MachineProfile.default("tpu:TPU v5 lite")
        assert t["t_compute"] == pytest.approx(1e15 / tpu.peak_flops)
        assert t["t_memory"] == pytest.approx(1e12 / tpu.hbm_bw)
        assert t["dominant"] == "compute"
        assert t["useful_ratio"] == pytest.approx(0.5)
        assert t["fits"] is True
