"""``tdp.autotune`` — the Program-level tuner over ``Target.tuning``.

Deterministic throughout: measurement runs under an *injected fake
timer* (scripted per-candidate costs — the pluggable-timer contract), so
these tests assert selection logic, pruning, caching and correctness
decoupling without ever depending on wall-clock noise:

* **best-candidate selection** — argmin of the scripted medians, with
  the base target always measured as candidate 0 (tuned median ≤
  default median by construction);
* **space construction** — executor axis capability-checked, the
  ``plane_block`` divisor sweep, VMEM-infeasibility pruning;
* **cache** — miss measures + writes ``<cache_dir>/<key>.json``, hit
  replays the stored choice without calling the timer at all;
* **correctness decoupling** — 5-step LB trajectories agree under the
  cross-path contract (``tdp.check_fields``) for *every* candidate in a
  small space (xla vs tuned pallas_interpret /
  pallas_windowed_interpret), and ``check_outputs=True`` keeps the
  honest candidates and prunes an executor that lies.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import tdp
from repro.core import Lattice, STENCIL_GRAD_6PT
from repro.core.autotune import cache_key
from repro.lb import programs as lbp
from repro.lb.params import LBParams

GRID = (8, 8, 8)
PARAMS = LBParams(A=0.125, B=0.125, kappa=0.02)
WT = tdp.Target("pallas_windowed", interpret=True)


def fused_prog(mode="two_launch"):
    return lbp.fused_program(
        mode, lbp.collision_consts(**PARAMS.as_kwargs()))


def lb_state(grid=GRID, seed=0):
    rng = np.random.default_rng(seed)
    f = jnp.asarray(0.05 * rng.normal(size=(19,) + grid) + 1 / 19.,
                    jnp.float32)
    g = jnp.asarray(0.05 * rng.normal(size=(19,) + grid), jnp.float32)
    return {"f": f, "g": g}


class ScriptedTimer:
    """Fake timer: cost per candidate label, call log kept."""

    def __init__(self, costs, default=1.0):
        self.costs = dict(costs)
        self.default = default
        self.calls = []

    def __call__(self, target, run):
        label = tdp.Candidate.of(target).label
        self.calls.append(label)
        for key, cost in self.costs.items():
            if key in label:
                return cost
        return self.default


@tdp.kernel(fields=[tdp.field(2)], out=2)
def double2(x):
    return 2.0 * x


@tdp.kernel(fields=[tdp.field(1, stencil=STENCIL_GRAD_6PT)], out=1)
def star_sum(p):
    acc = p[0, 0]
    for i in range(1, 7):
        acc = acc + p[i, 0]
    return acc[None]


# ---------------------------------------------------------------------------
# space construction
# ---------------------------------------------------------------------------

class TestSpace:
    def test_program_space_has_base_xla_and_divisor_sweep(self):
        cands, pruned = tdp.default_space(fused_prog(), WT, grid_shape=GRID)
        labels = [c.label for c in cands]
        assert labels[0] == "pallas_windowed_interpret"      # the base
        assert "xla" in labels
        pbs = [dict(c.tuning)["plane_block"] for c in cands
               if "plane_block" in dict(c.tuning)]
        assert pbs == [1, 2, 4, 8]                           # divisors of 8
        assert all(GRID[0] % p == 0 for p in pbs)
        assert pruned == []

    def test_vmem_limit_prunes_large_plane_blocks(self):
        cands, pruned = tdp.default_space(fused_prog(), WT, grid_shape=GRID,
                                          vmem_limit=1)
        assert all("plane_block" not in dict(c.tuning) for c in cands)
        assert pruned and all("vmem estimate" in why for _, why in pruned)

    def test_pointwise_spec_excludes_halo_extended_executors(self):
        x = jnp.ones((2, 32), jnp.float32)
        cands, pruned = tdp.default_space(
            double2, tdp.Target("xla"),
            executors=("xla", "pallas_windowed"))
        labels = [c.label for c in cands]
        assert "pallas_windowed" not in labels
        assert any("halo_extended" in why for _, why in pruned)
        del x

    def test_pointwise_pallas_axis_sweeps_declared_block_knobs(self):
        cands, _ = tdp.default_space(
            double2, tdp.Target("xla"),
            executors=("xla", "pallas_interpret"))
        knobs = {k for c in cands for k, _ in c.tuning}
        assert "block_f" in knobs                  # declared tunable
        assert "plane_block" not in knobs          # not on this executor

    def test_stencil_spec_plane_block_candidates(self):
        lat = Lattice((12, 4, 4))
        feasible, pruned = tdp.plane_block_candidates(star_sum, WT, lat)
        assert feasible == [1, 2, 3, 4, 6, 12]
        assert pruned == []
        feasible, pruned = tdp.plane_block_candidates(
            star_sum, WT, lat, vmem_limit=0)
        assert feasible == [] and len(pruned) == 6


# ---------------------------------------------------------------------------
# selection with a fake timer
# ---------------------------------------------------------------------------

class TestSelection:
    def test_best_candidate_wins(self, tmp_path):
        timer = ScriptedTimer({"plane_block=4": 0.01, "xla": 0.5},
                              default=1.0)
        tuned, report = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer,
            cache_dir=str(tmp_path), reps=3, warmup=0, measure_steps=1)
        assert report.best.label == "pallas_windowed_interpret[plane_block=4]"
        assert tuned.backend == "pallas_windowed" and tuned.interpret
        assert tuned.tune("plane_block") == 4
        assert report.best_median_s == pytest.approx(0.01)
        assert report.default_median_s == pytest.approx(1.0)
        assert report.best_median_s <= report.default_median_s

    def test_base_target_always_candidate_zero(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)   # flat costs: base wins ties
        tuned, report = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer,
            cache_dir=str(tmp_path), reps=1, warmup=0)
        assert report.results[0].candidate.label == \
            "pallas_windowed_interpret"
        assert tuned.executor == WT.executor

    def test_budget_keeps_base_and_prunes_tail(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        _, report = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer,
            cache_dir=str(tmp_path), budget=2, reps=1, warmup=0)
        assert len(report.results) == 2
        assert report.results[0].candidate.label == \
            "pallas_windowed_interpret"
        assert any("over budget" in why for _, why in report.pruned)

    def test_explicit_space_of_targets(self, tmp_path):
        timer = ScriptedTimer({"xla": 0.1}, default=1.0)
        tuned, report = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer,
            space=["xla", WT.with_tuning(plane_block=2)],
            cache_dir=str(tmp_path), reps=1, warmup=0)
        assert tuned.executor == "xla"
        # the base was prepended even though the space didn't name it
        assert report.results[0].candidate.label == \
            "pallas_windowed_interpret"

    def test_explicit_space_listing_base_elsewhere_keeps_it_first(
            self, tmp_path):
        """Candidate 0 is the base target even when the space lists it at
        a later index — the default-median baseline must be the base."""
        timer = ScriptedTimer({"xla": 0.1}, default=1.0)
        _, report = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer,
            space=["xla", WT], cache_dir=str(tmp_path), reps=1, warmup=0)
        labels = [r.candidate.label for r in report.results]
        assert labels[0] == "pallas_windowed_interpret"
        assert labels.count("pallas_windowed_interpret") == 1
        assert report.default_median_s == pytest.approx(1.0)   # not xla's

    def test_program_autotune_convenience(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        tuned, report = fused_prog().autotune(
            WT, lb_state(), timer=timer, cache_dir=str(tmp_path),
            reps=1, warmup=0)
        assert isinstance(report, tdp.TuneReport)
        assert tuned.executor == WT.executor

    def test_unrunnable_candidate_is_pruned_not_fatal(self, tmp_path):
        calls = {"n": 0}

        def exploding(target, run):
            calls["n"] += 1
            if target.executor == "xla":
                raise RuntimeError("boom")
            return 1.0

        _, report = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=exploding,
            cache_dir=str(tmp_path), reps=1, warmup=0)
        assert any("boom" in why for label, why in report.pruned
                   if label == "xla")
        assert all(r.candidate.label != "xla" for r in report.results)


# ---------------------------------------------------------------------------
# the on-disk cache
# ---------------------------------------------------------------------------

class TestCache:
    def test_miss_writes_then_hit_skips_measurement(self, tmp_path):
        timer = ScriptedTimer({"plane_block=2": 0.01}, default=1.0)
        prog = fused_prog()
        tuned1, rep1 = tdp.autotune(prog, WT, lb_state(), timer=timer,
                                    cache_dir=str(tmp_path), reps=1,
                                    warmup=0)
        assert not rep1.cache_hit
        path = os.path.join(str(tmp_path), f"{rep1.cache_key}.json")
        assert os.path.exists(path)
        n_calls = len(timer.calls)
        assert n_calls > 0

        tuned2, rep2 = tdp.autotune(prog, WT, lb_state(), timer=timer,
                                    cache_dir=str(tmp_path), reps=1,
                                    warmup=0)
        assert rep2.cache_hit
        assert len(timer.calls) == n_calls          # no re-measurement
        assert tuned2 == tuned1
        assert rep2.best == rep1.best

    def test_cache_key_discriminates_grid_backend_and_graph(self):
        prog = fused_prog()
        k = cache_key(prog, WT, (8, 8, 8))
        assert k != cache_key(prog, WT, (16, 8, 8))
        assert k != cache_key(prog, tdp.Target("xla"), (8, 8, 8))
        assert k != cache_key(fused_prog("one_launch"), WT, (8, 8, 8))
        # interpreter-measured tuning must never answer for compiled runs
        assert k != cache_key(prog, tdp.Target("pallas_windowed"),
                              (8, 8, 8))
        # stable across calls (no PYTHONHASHSEED dependence)
        assert k == cache_key(fused_prog(), WT, (8, 8, 8))

    def test_corrupt_cache_entry_is_a_miss(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        prog = fused_prog()
        _, rep = tdp.autotune(prog, WT, lb_state(), timer=timer,
                              cache_dir=str(tmp_path), reps=1, warmup=0)
        path = os.path.join(str(tmp_path), f"{rep.cache_key}.json")
        with open(path, "w") as fh:
            fh.write("{not json")
        _, rep2 = tdp.autotune(prog, WT, lb_state(), timer=timer,
                               cache_dir=str(tmp_path), reps=1, warmup=0)
        assert not rep2.cache_hit                   # re-measured
        with open(path) as fh:
            assert json.load(fh)["cache_key"] == rep.cache_key

    def test_interrupted_write_preserves_previous_entry(self, tmp_path,
                                                        monkeypatch):
        """A writer killed mid-``json.dump`` must not clobber the existing
        cache entry: the dump goes to a tempfile and only a completed one
        is ``os.replace``d over the real path."""
        import importlib
        at = importlib.import_module("repro.core.autotune")

        timer = ScriptedTimer({"plane_block=2": 0.01}, default=1.0)
        prog = fused_prog()
        _, rep1 = tdp.autotune(prog, WT, lb_state(), timer=timer,
                               cache_dir=str(tmp_path), reps=1, warmup=0)
        path = os.path.join(str(tmp_path), f"{rep1.cache_key}.json")
        with open(path) as fh:
            before = fh.read()

        real_dump = json.dump

        def dying_dump(obj, fh, **kw):
            fh.write('{"cache_key": "half-writ')    # partial bytes...
            fh.flush()
            raise KeyboardInterrupt("killed mid-write")   # ...then death

        monkeypatch.setattr(at.json, "dump", dying_dump)
        rep_fake = at.TuneReport.from_dict(rep1.as_dict(), cache_hit=False)
        with pytest.raises(KeyboardInterrupt):
            at.store_cached(str(tmp_path), rep_fake)
        monkeypatch.setattr(at.json, "dump", real_dump)

        with open(path) as fh:
            assert fh.read() == before          # old entry intact
        assert json.loads(before)["cache_key"] == rep1.cache_key
        # no orphaned tempfiles left behind
        leftovers = [n for n in os.listdir(str(tmp_path))
                     if n.endswith(".tmp")]
        assert leftovers == []
        # and the entry still replays as a hit
        _, rep2 = tdp.autotune(prog, WT, lb_state(), timer=timer,
                               cache_dir=str(tmp_path), reps=1, warmup=0)
        assert rep2.cache_hit

    def test_concurrent_writers_leave_valid_entry(self, tmp_path):
        """N threads racing ``store_cached`` on the same key: the final
        file is one complete JSON document (some writer's replace wins
        whole — never an interleaving)."""
        import importlib
        import threading

        at = importlib.import_module("repro.core.autotune")

        timer = ScriptedTimer({}, default=1.0)
        _, rep = tdp.autotune(fused_prog(), WT, lb_state(), timer=timer,
                              cache_dir=str(tmp_path), reps=1, warmup=0)
        path = os.path.join(str(tmp_path), f"{rep.cache_key}.json")
        errs = []

        def write(i):
            try:
                r = at.TuneReport.from_dict(rep.as_dict(), cache_hit=False)
                for _ in range(20):
                    at.store_cached(str(tmp_path), r)
            except Exception as e:       # pragma: no cover - failure path
                errs.append(e)

        threads = [threading.Thread(target=write, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errs == []
        with open(path) as fh:
            assert json.load(fh)["cache_key"] == rep.cache_key
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.endswith(".tmp")]

    def test_cache_dir_none_disables(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        _, rep = tdp.autotune(fused_prog(), WT, lb_state(), timer=timer,
                              cache_dir=None, reps=1, warmup=0)
        assert not rep.cache_hit
        assert os.listdir(str(tmp_path)) == []

    def test_report_round_trips_through_json(self, tmp_path):
        timer = ScriptedTimer({"xla": 0.25}, default=1.0)
        _, rep = tdp.autotune(fused_prog(), WT, lb_state(), timer=timer,
                              cache_dir=str(tmp_path), reps=2, warmup=0)
        rebuilt = tdp.TuneReport.from_dict(rep.as_dict(), cache_hit=True)
        assert rebuilt.best == rep.best
        assert rebuilt.results == rep.results
        assert rebuilt.cache_key == rep.cache_key
        assert rebuilt.cache_hit


# ---------------------------------------------------------------------------
# correctness is decoupled from tuning
# ---------------------------------------------------------------------------

class TestCorrectnessDecoupling:
    @pytest.mark.parametrize("mode", ["one_launch", "two_launch"])
    def test_five_step_trajectories_agree_under_all_candidates(self, mode):
        """Every candidate in the small space — xla and the tuned
        pallas_interpret / pallas_windowed_interpret variants — steps the
        LB program to the same 5-step trajectory under the cross-path
        contract."""
        prog = fused_prog(mode)
        state = lb_state()
        space = [
            tdp.Target("xla"),
            tdp.Target("pallas_interpret"),
            WT,                                       # plane_block default
            WT.with_tuning(plane_block=2),
            WT.with_tuning(plane_block=4),
        ]
        ref = None
        for tgt in space:
            exe = prog.compile(tgt, grid_shape=GRID)
            out = exe.run(dict(state), 5)
            if ref is None:
                ref = out
                continue
            tdp.check_fields(ref, out)

    def test_check_outputs_accepts_honest_candidates(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer,
            cache_dir=str(tmp_path), reps=1, warmup=0, measure_steps=2,
            check_outputs=True)
        # xla and every feasible plane_block variant all survive
        assert {r.candidate.label for r in rep.results} >= {
            "pallas_windowed_interpret", "xla"}
        assert not any("differs" in why for _, why in rep.pruned)

    def test_check_outputs_prunes_a_lying_executor(self, tmp_path):
        def lying(plan, prepared):
            outs = tdp.xla_executor(plan, prepared)
            return tuple(o + 1e-3 for o in outs)

        tdp.register_executor("lying_xla", lying)
        try:
            timer = ScriptedTimer({"lying_xla": 0.001}, default=1.0)
            tuned, rep = tdp.autotune(
                fused_prog(), tdp.Target("xla"), lb_state(), timer=timer,
                space=[tdp.Target("lying_xla")], cache_dir=str(tmp_path),
                reps=1, warmup=0, check_outputs=True)
            why = dict(rep.pruned)["lying_xla"]
            assert "differs from the default target" in why
            assert "field 'f'" in why and "largest gap" in why
            assert tuned.executor == "xla"      # cheapest honest candidate
        finally:
            tdp.unregister_executor("lying_xla")


# ---------------------------------------------------------------------------
# predictor-guided search (the costmodel scorer + top_k)
# ---------------------------------------------------------------------------

def scripted_scorer(costs, default=0.05):
    """Fake scorer keyed by label substring, mirroring ScriptedTimer."""
    def scorer(target):
        label = tdp.Candidate.of(target).label
        for key, cost in costs.items():
            if key in label:
                return cost
        return default
    return scorer


class TestPredictorGuided:
    def test_top_k_measures_at_most_k_plus_one(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        scorer = scripted_scorer({"plane_block=4": 0.001,
                                  "plane_block=2": 0.002})
        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer, scorer=scorer,
            top_k=2, cache_dir=str(tmp_path), reps=1, warmup=0)
        measured = [r.candidate.label for r in rep.results]
        assert len(measured) <= 3                      # K + the base
        assert "pallas_windowed_interpret[plane_block=4]" in measured
        assert "pallas_windowed_interpret[plane_block=2]" in measured

    def test_candidate_zero_never_model_pruned(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        # the base target scores WORST — it must still be measured
        scorer = scripted_scorer({}, default=0.001)

        def worst_for_base(target):
            label = tdp.Candidate.of(target).label
            return 99.0 if label == "pallas_windowed_interpret" else 0.001

        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer,
            scorer=worst_for_base, top_k=1, cache_dir=str(tmp_path),
            reps=1, warmup=0)
        assert rep.results[0].candidate.label == "pallas_windowed_interpret"
        assert not any(label == "pallas_windowed_interpret"
                       for label, _ in rep.pruned)

    def test_model_pruned_candidates_recorded_with_reason(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        scorer = scripted_scorer({"plane_block=4": 0.001})
        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer, scorer=scorer,
            top_k=1, cache_dir=str(tmp_path), reps=1, warmup=0)
        mp = [(label, why) for label, why in rep.pruned
              if why.startswith("model-pruned")]
        assert mp, "pruned-by-the-model candidates must be recorded"
        assert all("predicted rank" in why for _, why in mp)

    def test_unscored_candidates_pruned_not_crashed(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)

        def flaky(target):
            label = tdp.Candidate.of(target).label
            if "plane_block" in label:
                raise RuntimeError("no estimate for you")
            return 0.01

        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer, scorer=flaky,
            top_k=2, cache_dir=str(tmp_path), reps=1, warmup=0)
        assert any("no estimate" in why for _, why in rep.pruned)
        assert rep.results     # the runnable scored set still measured

    def test_predictions_annotate_results_and_round_trip(self, tmp_path):
        timer = ScriptedTimer({"plane_block=4": 0.01}, default=0.1)
        scorer = scripted_scorer({"plane_block=4": 0.005}, default=0.2)
        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer, scorer=scorer,
            cache_dir=str(tmp_path), reps=1, warmup=0)
        for r in rep.results:
            assert r.predicted_s is not None
            assert r.predicted_vs_measured == pytest.approx(
                (r.predicted_s - r.median_s) / r.median_s)
        assert rep.rank_correlation is not None
        rebuilt = tdp.TuneReport.from_dict(rep.as_dict(), cache_hit=True)
        assert rebuilt.results == rep.results
        assert rebuilt.rank_correlation == pytest.approx(
            rep.rank_correlation)

    def test_perfect_scorer_gives_rank_correlation_one(self, tmp_path):
        costs = {"plane_block=4": 0.01, "plane_block=2": 0.02, "xla": 0.5}
        timer = ScriptedTimer(costs, default=1.0)
        scorer = scripted_scorer(
            {k: v / 10 for k, v in costs.items()}, default=0.1)
        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer, scorer=scorer,
            cache_dir=str(tmp_path), reps=1, warmup=0)
        assert rep.rank_correlation == pytest.approx(1.0)

    def test_default_costmodel_scorer_scores_everything(self, tmp_path):
        timer = ScriptedTimer({}, default=1.0)
        _, rep = tdp.autotune(
            fused_prog(), WT, lb_state(), timer=timer, top_k=2,
            cache_dir=str(tmp_path), reps=1, warmup=0)
        assert len(rep.results) <= 3
        assert all(r.predicted_s is not None and r.predicted_s > 0
                   for r in rep.results)


# ---------------------------------------------------------------------------
# cache schema versioning
# ---------------------------------------------------------------------------

class TestCacheSchema:
    def _one_report(self, tmp_path):
        timer = ScriptedTimer({"xla": 0.25}, default=1.0)
        _, rep = tdp.autotune(fused_prog(), WT, lb_state(), timer=timer,
                              cache_dir=str(tmp_path), reps=1, warmup=0)
        (entry,) = [n for n in os.listdir(str(tmp_path))
                    if n.endswith(".json")]
        return rep, os.path.join(str(tmp_path), entry)

    def test_entries_carry_current_schema(self, tmp_path):
        rep, path = self._one_report(tmp_path)
        from repro.core.autotune import SCHEMA_VERSION
        assert rep.schema == SCHEMA_VERSION == 3
        with open(path) as fh:
            assert json.load(fh)["schema"] == SCHEMA_VERSION

    def test_v1_entry_still_replays(self, tmp_path):
        rep, path = self._one_report(tmp_path)
        d = json.load(open(path))
        del d["schema"]                        # v1 entries had no field
        del d["rank_correlation"]
        for r in d["candidates"]:
            r.pop("predicted_s", None)
            r.pop("predicted_vs_measured", None)
        json.dump(d, open(path, "w"))
        timer = ScriptedTimer({}, default=1.0)
        _, rep2 = tdp.autotune(fused_prog(), WT, lb_state(), timer=timer,
                               cache_dir=str(tmp_path), reps=1, warmup=0)
        assert rep2.cache_hit
        assert timer.calls == []
        assert rep2.best == rep.best
        assert all(r.predicted_s is None for r in rep2.results)

    def test_future_schema_is_a_miss(self, tmp_path):
        rep, path = self._one_report(tmp_path)
        d = json.load(open(path))
        d["schema"] = 99
        json.dump(d, open(path, "w"))
        timer = ScriptedTimer({}, default=1.0)
        _, rep2 = tdp.autotune(fused_prog(), WT, lb_state(), timer=timer,
                               cache_dir=str(tmp_path), reps=1, warmup=0)
        assert not rep2.cache_hit
        assert timer.calls != []               # re-measured from scratch


# ---------------------------------------------------------------------------
# per-stage tuning assignments
# ---------------------------------------------------------------------------

class TestPerStage:
    def test_space_gains_stage_candidates(self):
        cands, _ = tdp.default_space(
            fused_prog("two_launch"), WT, grid_shape=GRID,
            executors=["pallas_windowed"], per_stage=True)
        stage_keys = {k for c in cands for k, _ in c.tuning
                      if k.startswith("stage:")}
        assert stage_keys == {"stage:phi_stream", "stage:fused_two"}

    def test_single_windowed_stage_skips_the_axis(self):
        # one windowed stage makes per-stage ≡ the global sweep
        cands, _ = tdp.default_space(
            fused_prog("one_launch"), WT, grid_shape=GRID,
            executors=["pallas_windowed"], per_stage=True)
        assert not any(k.startswith("stage:")
                       for c in cands for k, _ in c.tuning)

    def test_resolve_stage_target_merges_only_its_stage(self):
        from repro.core.program import resolve_stage_target
        prog = fused_prog("two_launch")
        tgt = WT.with_tuning({"stage:fused_two": (("plane_block", 4),)})
        pplan = prog.plan(tgt, grid_shape=GRID)
        by_stage = {n: p.target.tuning for n, p in pplan.stages}
        assert by_stage["fused_two"] == (("plane_block", 4),)
        assert by_stage["phi_stream"] == ()
        del resolve_stage_target

    def test_per_stage_candidates_run_bit_identical(self):
        """Another ``plane_block`` per stage is another blocking: held to
        the base target under the cross-path contract."""
        prog = fused_prog("two_launch")
        state = lb_state()
        base = prog.compile(WT, grid_shape=GRID)
        ref = base.run(dict(state), 3)
        for skey in ("stage:phi_stream", "stage:fused_two"):
            tgt = WT.with_tuning({skey: (("plane_block", 4),)})
            tdp.check_fields(
                ref, prog.compile(tgt, grid_shape=GRID).run(dict(state), 3))

    def test_per_stage_autotune_round_trips_nested_tuning(self, tmp_path):
        skey = "pallas_windowed_interpret[stage:fused_two{plane_block=4}]"
        timer = ScriptedTimer({"stage:fused_two{plane_block=4}": 0.01},
                              default=1.0)
        tuned, rep = tdp.autotune(
            fused_prog("two_launch"), WT, lb_state(), timer=timer,
            executors=["pallas_windowed"], per_stage=True,
            cache_dir=str(tmp_path), reps=1, warmup=0)
        assert rep.best.label == skey
        assert dict(tuned.tuning)["stage:fused_two"] == \
            (("plane_block", 4),)
        # warm replay restores the nested choice exactly
        timer2 = ScriptedTimer({}, default=1.0)
        tuned2, rep2 = tdp.autotune(
            fused_prog("two_launch"), WT, lb_state(), timer=timer2,
            executors=["pallas_windowed"], per_stage=True,
            cache_dir=str(tmp_path), reps=1, warmup=0)
        assert rep2.cache_hit and timer2.calls == []
        assert dict(tuned2.tuning)["stage:fused_two"] == \
            (("plane_block", 4),)
