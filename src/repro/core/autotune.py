"""``tdp.autotune`` — close the paper's tuning loop over ``Target.tuning``.

The paper's portability claim is explicitly *tuned* portability: one
source, with per-platform decomposition knobs (TLP/ILP split, SIMD
vector length) chosen to fit the hardware, and its sequel ("A
Lightweight Approach to Performance Portability with targetDP",
1609.01479) states that those knobs must be re-chosen per device.  This
framework exposes the knobs — the executor choice, ``vvl``,
``Target.tuning["plane_block"]``, the pointwise block sizes — but until
now choosing them was manual.  :func:`autotune` closes the loop:

1. **enumerate** a candidate space of :class:`Candidate` assignments,
   derived from the program/spec and the launch geometry unless given
   explicitly: the executor axis comes from
   :func:`repro.core.registry.compatible_executors` (capability-checked
   against the spec's stencil needs), ``plane_block`` sweeps the
   *divisors* of the launch's x-plane count for ``wants="halo_extended"``
   executors, and the pointwise Pallas block knobs sweep
   :data:`POINTWISE_TUNABLE_VALUES` where the executor declares them;
2. **prune** infeasible candidates up front — a candidate whose
   :meth:`~repro.core.api.LaunchPlan.vmem_bytes_estimate` (max over
   stages, for a Program) exceeds ``vmem_limit`` is never measured;
3. **measure** each survivor with a pluggable ``timer`` (median over
   ``reps`` calls of a ``measure_steps``-step run; real wall clock by
   default, injectable fake for deterministic tests);
4. **return** a frozen tuned :class:`~repro.core.target.Target` (the
   base target with the winning candidate's backend + merged tuning)
   plus a :class:`TuneReport` (per-candidate medians, the pruned list,
   the cache key).

Correctness is decoupled from tuning by construction — candidates only
permute *how* the same launches execute, never *what* they compute; the
optional ``check_outputs=True`` verifies this at tune time by holding
every candidate's output to the default target's under
:func:`repro.core.state.check_fields`, the cross-path contract (another
executor or blocking rounds the last bit differently; a mismatch beyond
the contract is pruned, not chosen).  The base target is always
candidate 0, so the tuned median can never exceed the default median.

Results persist in an on-disk cache (``results/tuning/`` by default)
keyed by (program/spec digest, grid, backend family, device kind) —
repeated runs skip measurement entirely and reproduce the same choice
(``TuneReport.cache_hit``).

Two extensions ride on :mod:`repro.core.costmodel`:

* **predictor-guided search** — every candidate is scored by the
  analytical roofline model before measurement (``scorer=`` overrides
  the default :func:`repro.core.costmodel.predict` scorer); with
  ``top_k=K`` only the base target plus the K best-predicted candidates
  are measured (at most K+1 measurements), the rest recorded in
  ``report.pruned`` with a ``model-pruned`` reason.  Every measured
  candidate records ``predicted_s`` and the ``predicted_vs_measured``
  relative error, and the report carries the Spearman
  ``rank_correlation`` between predicted and measured over the measured
  set — the running proof (or refutation) that the model ranks.
* **per-stage tuning** (``per_stage=True``) — program-level candidates
  may assign a distinct ``plane_block`` per windowed :class:`Stage` via
  the reserved ``Target.tuning`` keys ``"stage:<name>"`` (value: a
  frozen tuple of ``(knob, value)`` pairs, merged over the flat tuning
  by :func:`repro.core.program.resolve_stage_target`).  Cache entries
  are schema-versioned (:data:`SCHEMA_VERSION`): older entries replay,
  entries from a future schema miss cleanly.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import tempfile
import time
from typing import Any, Callable, Mapping, NamedTuple, Sequence

import jax
import numpy as np

from . import costmodel as _costmodel
from .api import launch as _launch
from .api import launch_plan as _launch_plan
from .lattice import Lattice
from .program import CompiledProgram, Program
from .registry import (
    compatible_executors,
    executor_tunables,
    executor_wants,
)
from .spec import KernelSpec
from .state import check_fields
from .target import Target, as_target

#: on-disk cache entry schema.  v1: PR 5 entries (no predictor fields,
#: no per-stage tuning).  v2: adds ``schema``, per-candidate
#: ``predicted_s`` / ``predicted_vs_measured``, report-level
#: ``rank_correlation``, and nested ``stage:<name>`` tuning values.
#: v3: adds the per-candidate ``vvl`` / ``layout`` axes (ISSUE 10 —
#: the AoSoA layout sweep); absent fields replay as ``None`` (inherit
#: the base target), so v1/v2 entries keep replaying.
#: Older entries replay (missing fields default); entries written by a
#: *future* schema are a cache miss, never a parse error.
SCHEMA_VERSION = 3

#: default candidate values for the pointwise Pallas block knobs
#: (consulted per executor: only keys the executor *declares* via
#: ``register_executor(..., tunables=...)`` are swept).
POINTWISE_TUNABLE_VALUES: dict[str, tuple[int, ...]] = {
    "block_f": (256, 512, 1024),
    "block_q": (64, 128, 256),
    "block_k": (64, 128, 256),
    "block_d": (64, 128),
    "block_t": (64, 128),
}


# ---------------------------------------------------------------------------
# candidates
# ---------------------------------------------------------------------------

def _freeze_value(v):
    """Hashable, canonical form of one tuning value.  Nested mappings
    (and JSON round-tripped lists of pairs) become sorted tuples of
    pairs — the per-stage ``"stage:<name>"`` values."""
    if isinstance(v, Mapping):
        return tuple(sorted((str(k), _freeze_value(x))
                            for k, x in v.items()))
    if isinstance(v, (list, tuple)):
        if v and all(isinstance(x, (list, tuple)) and len(x) == 2
                     and isinstance(x[0], str) for x in v):
            return tuple(sorted((str(k), _freeze_value(x)) for k, x in v))
        return tuple(_freeze_value(x) for x in v)
    return v


def _freeze_items(mapping) -> tuple[tuple[str, Any], ...]:
    if not mapping:
        return ()
    items = (mapping.items() if isinstance(mapping, Mapping)
             else (tuple(kv) for kv in mapping))
    return tuple(sorted((str(k), _freeze_value(v)) for k, v in items))


def _is_pairs(v) -> bool:
    return (isinstance(v, tuple) and len(v) > 0
            and all(isinstance(x, tuple) and len(x) == 2
                    and isinstance(x[0], str) for x in v))


def _json_value(v):
    """The JSON-serialisable form of a frozen tuning value (inverse of
    :func:`_freeze_value` up to key order)."""
    if _is_pairs(v):
        return {k: _json_value(x) for k, x in v}
    if isinstance(v, tuple):
        return [_json_value(x) for x in v]
    return v


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the tuning space: an executor assignment plus the
    ``Target.tuning`` knobs to merge in.

    ``backend`` is a registry name (the ``"..._interpret"`` spellings
    canonicalise through :class:`Target` as usual); ``tuning`` is merged
    into — never replaces — the base target's tuning, so unrelated knobs
    ride through unchanged.  ``vvl`` / ``layout`` (schema v3, ISSUE 10)
    are the Target-level memory axes: ``None`` inherits the base
    target's value, a set value overrides it (``layout="aosoa"``
    candidates sweep the paper's AoSoA ordering; ``vvl`` both sets the
    gathered chunk size and the AoSoA inner block width).
    """

    backend: str
    interpret: bool = False
    tuning: tuple[tuple[str, Any], ...] = ()
    vvl: int | None = None
    layout: str | None = None

    def __post_init__(self):
        object.__setattr__(self, "tuning", _freeze_items(self.tuning))
        if self.vvl is not None:
            object.__setattr__(self, "vvl", int(self.vvl))
        if self.layout is not None and self.layout not in ("soa", "aosoa"):
            raise ValueError(f"layout must be 'soa', 'aosoa' or None "
                             f"(inherit), got {self.layout!r}")

    def target_from(self, base: Target) -> Target:
        t = base.with_(backend=self.backend, interpret=self.interpret)
        if self.vvl is not None:
            t = t.with_(vvl=self.vvl)
        if self.layout is not None:
            t = t.with_(layout=self.layout)
        return t.with_tuning(dict(self.tuning)) if self.tuning else t

    @property
    def label(self) -> str:
        name = self.backend
        if self.interpret and not name.endswith("_interpret"):
            name += "_interpret"
        knobs = []
        if self.layout is not None:
            knobs.append(f"layout={self.layout}")
        if self.vvl is not None:
            knobs.append(f"vvl={self.vvl}")
        knobs += [(f"{k}{{{','.join(f'{ik}={iv}' for ik, iv in v)}}}"
                   if _is_pairs(v) else f"{k}={v}")
                  for k, v in self.tuning]
        return f"{name}[{','.join(knobs)}]" if knobs else name

    def as_dict(self) -> dict:
        return {"backend": self.backend, "interpret": self.interpret,
                "tuning": {k: _json_value(v) for k, v in self.tuning},
                "vvl": self.vvl, "layout": self.layout}

    @classmethod
    def from_dict(cls, d: Mapping) -> "Candidate":
        vvl = d.get("vvl")
        return cls(d["backend"], bool(d.get("interpret", False)),
                   _freeze_items(d.get("tuning") or {}),
                   None if vvl is None else int(vvl),
                   d.get("layout"))

    @classmethod
    def of(cls, target: Target) -> "Candidate":
        """The candidate that reproduces ``target``'s dispatch.

        ``vvl`` / ``layout`` stay ``None`` (inherit) deliberately:
        candidate 0 must dispatch *exactly* as the base target does,
        including a ``vvl=None`` target re-resolving the process default
        at launch time."""
        return cls(target.backend, target.interpret, target.tuning)


def _divisors(n: int) -> list[int]:
    n = int(n)
    small, large = [], []
    for d in range(1, int(math.isqrt(n)) + 1):
        if n % d == 0:
            small.append(d)
            if d != n // d:
                large.append(n // d)
    return small + large[::-1]


def _vvl_values(n: int, *, lo: int = 8, hi: int = 8192,
                max_values: int = 6) -> list[int]:
    """The vvl sweep for a launch over ``n`` sites (or, for the windowed
    AoSoA path, ``n`` sites per x-plane): divisors of ``n`` in
    ``[lo, hi]``, thinned to at most ``max_values`` evenly spaced points
    (keeping the extremes) so a highly composite site count doesn't
    explode the space."""
    n = int(n)
    if n <= 0:
        return []
    vals = [d for d in _divisors(n) if lo <= d <= hi]
    if not vals:
        return [n] if n < lo else []
    if len(vals) > max_values:
        idx = np.linspace(0, len(vals) - 1, max_values).round().astype(int)
        vals = sorted({vals[i] for i in idx})
    return vals


def plane_block_candidates(spec: KernelSpec, target: Target | str | None,
                           lattice: Lattice, *, halo=None, consts=None,
                           vmem_limit: int | None = None):
    """The ``plane_block`` axis for one ``wants="halo_extended"`` launch.

    Emits the divisors of the launch's x-plane count (``plan.shape[0]``
    — for a Program stage that is the *extended* plane count, interior +
    recompute ring) whose windowed-executor VMEM model fits
    ``vmem_limit`` (default: :func:`~repro.core.costmodel.vmem_limit_bytes`
    for the target, the cap the compiler is given).  Divisors, not every
    integer: the executor pads the grid to a ``plane_block`` multiple, so
    non-divisors waste whole padded planes per step.  Each estimate is of
    the plan a launch would build: y-blocked
    (:attr:`~repro.core.api.LaunchPlan.y_block`) where the whole-plane
    window exceeds the device's limit.

    Returns ``(feasible, pruned)`` — ``feasible`` the surviving
    ``plane_block`` values, ``pruned`` a list of ``(value, reason)``.
    """
    tgt = as_target(target)
    if vmem_limit is None:
        vmem_limit = _costmodel.vmem_limit_bytes(tgt.interpret)
    feasible: list[int] = []
    pruned: list[tuple[int, str]] = []
    base_plan = _launch_plan(spec, tgt, lattice=lattice, halo=halo,
                             consts=consts)
    for p in _divisors(base_plan.shape[0]):
        plan = _launch_plan(spec, tgt.with_tuning(plane_block=p),
                            lattice=lattice, halo=halo, consts=consts)
        vmem = plan.vmem_bytes_estimate()
        if vmem <= vmem_limit:
            feasible.append(p)
        else:
            pruned.append((p, f"vmem estimate {vmem} > limit {vmem_limit}"))
    return feasible, pruned


def _program_plane_counts(program: Program, target: Target,
                          grid_shape) -> list[int]:
    """x-plane counts of every stage a ``halo_extended`` executor would
    actually run (stencil stages; pointwise stages route to xla)."""
    pplan = program.plan(target, grid_shape=grid_shape)
    return [p.shape[0] for _, p in pplan.stages
            if p.wants == "halo_extended" and p.shape is not None]


def default_space(program_or_spec, target: Target | str | None = None, *,
                  grid_shape: Sequence[int] | None = None,
                  lattice: Lattice | None = None, halo=None, consts=None,
                  executors: Sequence[str] | None = None,
                  vmem_limit: int | None = None,
                  per_stage: bool = False,
                  site_count: int | None = None):
    """Derive the default candidate space for :func:`autotune`.

    Axes (the candidate-space table in docs/targetdp_api.md):

    * the **base target itself** — always candidate 0, so the tuned
      median is ≤ the default median by construction;
    * the **executor axis** — ``executors`` if given, else the base
      executor + ``"xla"``, intersected with
      :func:`~repro.core.registry.compatible_executors` for the spec's
      capability needs (a pointwise-only spec never meets a
      ``halo_extended`` executor);
    * per ``wants="halo_extended"`` executor, the **plane_block
      divisor sweep** (:func:`plane_block_candidates`), VMEM-filtered;
    * per executor that declares pointwise block knobs
      (``executor_tunables``), one candidate per value in
      :data:`POINTWISE_TUNABLE_VALUES`;
    * per ``wants="gathered"`` executor, the **vvl sweep**
      (:func:`_vvl_values` — divisors of the launch's site count,
      thinned and VMEM-filtered; needs ``site_count`` / ``lattice`` /
      ``grid_shape`` to know the count) and the **layout axis**: one
      ``layout="aosoa"`` candidate per surviving vvl (gathered AoSoA
      pads remainder sites, so every vvl is valid);
    * per ``wants="halo_extended"`` executor, the **layout axis**:
      ``layout="aosoa"`` candidates over vvl divisors of the (gcd of
      the windowed stages') interior x-plane site count — the windowed
      AoSoA validity contract (:func:`repro.core.api.launch`),
      VMEM-filtered;
    * with ``per_stage=True``, for programs with **more than one**
      windowed stage, an independent per-stage ``plane_block`` sweep:
      one candidate per (stage, divisor-of-that-stage's-plane-count)
      under the reserved tuning key ``"stage:<name>"`` (a single
      windowed stage makes per-stage ≡ global, so the axis is skipped).

    Returns ``(candidates, pruned)`` where ``pruned`` is a list of
    ``(label, reason)`` for space points rejected before measurement.
    """
    base = as_target(target)
    if vmem_limit is None:
        vmem_limit = _costmodel.vmem_limit_bytes(base.interpret)
    is_program = isinstance(program_or_spec, Program)
    if is_program:
        has_stencil = any(st.spec.has_stencil
                          for st in program_or_spec.stages)
        if grid_shape is None:
            raise ValueError("default_space over a Program needs "
                             "grid_shape")
    elif isinstance(program_or_spec, KernelSpec):
        has_stencil = program_or_spec.has_stencil
        if has_stencil and lattice is None:
            raise ValueError("default_space over a stencil KernelSpec "
                             "needs the lattice")
    else:
        raise TypeError(f"expected a Program or KernelSpec, got "
                        f"{type(program_or_spec).__name__}")

    ok = set(compatible_executors(stencil=has_stencil))
    if executors is None:
        names = [base.executor, "xla"]
    else:
        names = [str(n) for n in executors]
    pruned: list[tuple[str, str]] = []
    seen: set[str] = set()
    axis: list[Candidate] = []
    for n in names:
        t = as_target(n)
        # inherit the base interpret flag when staying in the base's
        # backend family (a CPU host tuning pallas_windowed_interpret
        # must not emit the un-runnable hardware spelling)
        interpret = t.interpret or (base.interpret
                                    and t.backend == base.backend)
        cand = Candidate(t.backend, interpret)
        if cand.label in seen:
            continue
        seen.add(cand.label)
        if cand.backend not in ok:
            reason = ("not registered" if cand.backend not in
                      set(compatible_executors(stencil=True))
                      else "wants='halo_extended' but the launch has no "
                           "stencil field")
            pruned.append((cand.label, reason))
            continue
        axis.append(cand)

    candidates: list[Candidate] = [Candidate.of(base)]
    cand_seen = {candidates[0].label}

    def add(c: Candidate):
        if c.label not in cand_seen:
            cand_seen.add(c.label)
            candidates.append(c)

    if is_program:
        nsites = math.prod(int(s) for s in grid_shape)
    elif lattice is not None:
        nsites = math.prod(int(s) for s in lattice.shape)
    else:
        nsites = None if site_count is None else int(site_count)

    def vmem_of(c: Candidate) -> int:
        t = c.target_from(base)
        if is_program:
            return program_or_spec.plan(
                t, grid_shape=grid_shape).vmem_bytes_estimate()
        return _launch_plan(program_or_spec, t, lattice=lattice,
                            halo=halo, consts=consts).vmem_bytes_estimate()

    def add_vmem_checked(c: Candidate):
        try:
            vmem = vmem_of(c)
        except Exception as e:  # noqa: BLE001 — unplannable space point
            pruned.append((c.label, f"error: {type(e).__name__}: {e}"))
            return
        if vmem <= vmem_limit:
            add(c)
        else:
            pruned.append(
                (c.label, f"vmem estimate {vmem} > limit {vmem_limit}"))

    for cand in axis:
        add(cand)
        probe = cand.target_from(base)
        if executor_wants(cand.backend) == "halo_extended":
            if is_program:
                # divisors of every windowed stage's (extended) plane
                # count ≡ divisors of their gcd; feasibility is the
                # aggregated ProgramPlan VMEM model (max over stages)
                counts = _program_plane_counts(program_or_spec, probe,
                                               grid_shape)
                if not counts:
                    continue
                values = []
                for v in _divisors(math.gcd(*counts)):
                    pplan = program_or_spec.plan(
                        probe.with_tuning(plane_block=v),
                        grid_shape=grid_shape)
                    vmem = pplan.vmem_bytes_estimate()
                    if vmem <= vmem_limit:
                        values.append(v)
                    else:
                        pruned.append(
                            (f"{cand.label}[plane_block={v}]",
                             f"vmem estimate {vmem} > limit "
                             f"{vmem_limit}"))
            else:
                values, pr = plane_block_candidates(
                    program_or_spec, probe, lattice, halo=halo,
                    consts=consts, vmem_limit=vmem_limit)
                for v, why in pr:
                    pruned.append((f"{cand.label}[plane_block={v}]", why))
            for v in values:
                add(Candidate(cand.backend, cand.interpret,
                              ((("plane_block", int(v)),))))
            if per_stage and is_program:
                pplan0 = program_or_spec.plan(probe,
                                              grid_shape=grid_shape)
                stages_w = [(n, p.shape[0]) for n, p in pplan0.stages
                            if p.wants == "halo_extended"
                            and p.shape is not None]
                # one windowed stage: per-stage ≡ the global sweep
                if len(stages_w) > 1:
                    for sname, count in stages_w:
                        skey = f"stage:{sname}"
                        for v in _divisors(count):
                            if v == 1:
                                continue    # ≡ the default plane_block
                            nested = (("plane_block", int(v)),)
                            pplan = program_or_spec.plan(
                                probe.with_tuning({skey: nested}),
                                grid_shape=grid_shape)
                            vmem = pplan.vmem_bytes_estimate()
                            if vmem <= vmem_limit:
                                add(Candidate(cand.backend,
                                              cand.interpret,
                                              ((skey, nested),)))
                            else:
                                pruned.append(
                                    (f"{cand.label}[{skey}"
                                     f"{{plane_block={v}}}]",
                                     f"vmem estimate {vmem} > limit "
                                     f"{vmem_limit}"))
        elif not has_stencil:
            # pointwise launches: the block knobs the executor declares
            # (stencil programs route pointwise stages to xla, so the
            # knobs would be dead weight there)
            for key in executor_tunables(cand.backend):
                for v in POINTWISE_TUNABLE_VALUES.get(key, ()):
                    add(Candidate(cand.backend, cand.interpret,
                                  (((key, int(v)),))))

        # --- layout × vvl axes (ISSUE 10) -----------------------------
        if executor_wants(cand.backend) == "halo_extended":
            # windowed AoSoA: vvl must divide each windowed stage's
            # interior x-plane site count (plan-build contract in
            # repro.core.api._validate_layout) — sweep divisors of
            # their gcd
            if is_program:
                pplan = program_or_spec.plan(probe, grid_shape=grid_shape)
                counts = [
                    math.prod(int(s) for s in p.shape[1:])
                    for _, p in pplan.stages
                    if p.wants == "halo_extended" and p.shape is not None]
            elif lattice is not None:
                counts = [math.prod(int(s) for s in lattice.shape[1:])]
            else:
                counts = []
            counts = [c for c in counts if c > 0]
            if counts:
                for v in _vvl_values(math.gcd(*counts)):
                    add_vmem_checked(Candidate(cand.backend,
                                               cand.interpret,
                                               vvl=v, layout="aosoa"))
        elif nsites is not None:
            # gathered executors: the vvl sweep (SoA) plus one AoSoA
            # candidate per vvl — remainder sites pad, so every divisor
            # is valid
            for v in _vvl_values(nsites):
                if v != probe.resolve_vvl():   # ≡ the bare executor cand
                    add_vmem_checked(Candidate(cand.backend,
                                               cand.interpret, vvl=v))
                add_vmem_checked(Candidate(cand.backend, cand.interpret,
                                           vvl=v, layout="aosoa"))
    return candidates, pruned


# ---------------------------------------------------------------------------
# timers
# ---------------------------------------------------------------------------

def wall_clock_timer(candidate: Target, run: Callable[[], Any]) -> float:
    """The default timer: execute ``run`` once, block on its outputs,
    return elapsed wall-clock seconds.  The ``timer`` protocol — any
    ``(candidate_target, run) -> seconds`` callable — is the injection
    point for deterministic tests (a fake can script per-candidate costs
    and never execute anything)."""
    t0 = time.perf_counter()
    jax.block_until_ready(run())
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

class CandidateResult(NamedTuple):
    """One measured point: the candidate, its median, the raw samples,
    and (when a scorer ran) the model's prediction — ``predicted_s``
    seconds and ``predicted_vs_measured`` = (predicted − measured) /
    measured (positive: the model overestimates)."""

    candidate: Candidate
    median_s: float
    times_s: tuple[float, ...]
    predicted_s: float | None = None
    predicted_vs_measured: float | None = None

    def as_dict(self) -> dict:
        return {**self.candidate.as_dict(), "label": self.candidate.label,
                "median_s": self.median_s, "times_s": list(self.times_s),
                "predicted_s": self.predicted_s,
                "predicted_vs_measured": self.predicted_vs_measured}


@dataclasses.dataclass(frozen=True)
class TuneReport:
    """What :func:`autotune` measured and chose.

    ``results`` holds one :class:`CandidateResult` per measured
    candidate (measurement order; the base target is always first);
    ``pruned`` the ``(label, reason)`` pairs rejected before or during
    measurement; ``best`` the winning candidate; ``cache_hit`` whether
    the choice was replayed from the on-disk cache without measuring.
    """

    name: str
    grid: tuple[int, ...]
    device: str
    results: tuple[CandidateResult, ...]
    pruned: tuple[tuple[str, str], ...]
    best: Candidate
    default_median_s: float
    cache_key: str
    cache_hit: bool = False
    measure_steps: int = 1
    rank_correlation: float | None = None
    schema: int = SCHEMA_VERSION

    @property
    def best_median_s(self) -> float:
        for r in self.results:
            if r.candidate == self.best:
                return r.median_s
        raise ValueError(f"best candidate {self.best.label!r} has no "
                         f"measurement")

    def as_dict(self) -> dict:
        return {
            "schema": self.schema,
            "name": self.name, "grid": list(self.grid),
            "device": self.device,
            "measure_steps": self.measure_steps,
            "cache_key": self.cache_key, "cache_hit": self.cache_hit,
            "best": {**self.best.as_dict(), "label": self.best.label,
                     "median_s": self.best_median_s},
            "default_median_s": self.default_median_s,
            "rank_correlation": self.rank_correlation,
            "candidates": [r.as_dict() for r in self.results],
            "pruned": [{"label": l, "reason": r} for l, r in self.pruned],
        }

    @classmethod
    def from_dict(cls, d: Mapping, *, cache_hit: bool = False):
        def _opt(v):
            return None if v is None else float(v)

        return cls(
            name=d["name"], grid=tuple(d["grid"]), device=d["device"],
            results=tuple(
                CandidateResult(Candidate.from_dict(c),
                                float(c["median_s"]),
                                tuple(float(t) for t in c["times_s"]),
                                _opt(c.get("predicted_s")),
                                _opt(c.get("predicted_vs_measured")))
                for c in d["candidates"]),
            pruned=tuple((p["label"], p["reason"]) for p in d["pruned"]),
            best=Candidate.from_dict(d["best"]),
            default_median_s=float(d["default_median_s"]),
            cache_key=d["cache_key"], cache_hit=cache_hit,
            measure_steps=int(d.get("measure_steps", 1)),
            rank_correlation=_opt(d.get("rank_correlation")),
            schema=int(d.get("schema", 1)))


class TuneResult(NamedTuple):
    """``(target, report)`` — tuple-unpackable."""

    target: Target
    report: TuneReport


# ---------------------------------------------------------------------------
# cache (results/tuning/)
# ---------------------------------------------------------------------------

def _stencil_sig(s) -> str:
    return "-" if s is None else f"{s.name}:{s.offsets}"


def _spec_digest(spec: KernelSpec) -> str:
    """Stable (cross-process — no Python string hashing) identity of a
    spec's *launch shape*: roles, stencil geometry, outputs.  The kernel
    body is identified by name — tuning choices depend on the launch
    structure, not the arithmetic."""
    parts = [spec.name, repr(spec.out), repr(spec.site_index),
             repr(spec.consts)]
    for fs in spec.fields:
        parts.append(f"{fs.ncomp}|{fs.halo}|{_stencil_sig(fs.stencil)}")
    return hashlib.sha256("&".join(parts).encode()).hexdigest()[:16]


def _subject_digest(program_or_spec) -> tuple[str, str]:
    if isinstance(program_or_spec, Program):
        parts = [program_or_spec.name]
        for st in program_or_spec.stages:
            parts.append(f"{st.name}|{_spec_digest(st.spec)}|"
                         f"{st.reads}|{st.writes}")
        digest = hashlib.sha256("&".join(parts).encode()).hexdigest()[:16]
        return program_or_spec.name, digest
    return program_or_spec.name, _spec_digest(program_or_spec)


def cache_key(program_or_spec, target: Target,
              grid: tuple[int, ...]) -> str:
    """The cache-key anatomy (docs/targetdp_api.md, "Autotuning"):
    ``<name>-<subject digest>-g<grid>-<base executor>-<device kind>``,
    filesystem-safe.  Deliberately *excludes* the tuning values being
    searched — the key identifies the question, the cached file holds
    the answer."""
    name, digest = _subject_digest(program_or_spec)
    grid_s = "x".join(str(int(s)) for s in grid)
    dev = _costmodel._device_kind().replace(" ", "_").replace("/", "_")
    # Candidate.label spells interpret mode for every backend family
    # (Target.executor only does so for "pallas") — interpreter-measured
    # and compiled tuning runs must never share a cache entry.
    mode = Candidate(target.backend, target.interpret).label
    return f"{name}-{digest}-g{grid_s}-{mode}-{dev}"


def _cache_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, f"{key}.json")


def load_cached(cache_dir: str, key: str) -> TuneReport | None:
    """The stored :class:`TuneReport` for ``key``, or ``None`` on miss /
    unreadable file (a corrupt cache entry is a miss, not an error)."""
    path = _cache_path(cache_dir, key)
    try:
        with open(path) as fh:
            data = json.load(fh)
        if data.get("cache_key") != key:
            return None
        # schema gate: v1 (pre-predictor) entries replay with defaulted
        # fields; an entry written by a *newer* schema than this process
        # understands is a miss, not a parse error
        if int(data.get("schema", 1)) > SCHEMA_VERSION:
            return None
        return TuneReport.from_dict(data, cache_hit=True)
    except (OSError, ValueError, KeyError, TypeError):
        return None


def store_cached(cache_dir: str, report: TuneReport) -> str:
    """Atomically persist ``report`` under its cache key.

    The entry is serialised to a private tempfile in ``cache_dir`` (same
    filesystem, so the final rename is atomic) and ``os.replace``\\ d
    into place: an interrupted run can never leave a truncated entry
    behind, and concurrent writers (two bench processes sharing
    ``results/tuning/``) each land a complete file — last one wins."""
    os.makedirs(cache_dir, exist_ok=True)
    path = _cache_path(cache_dir, report.cache_key)
    fd, tmp = tempfile.mkstemp(dir=cache_dir,
                               prefix=f".{report.cache_key}-",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump(report.as_dict(), fh, indent=1, default=str)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

def _as_candidates(space) -> list[Candidate]:
    out = []
    for c in space:
        if isinstance(c, Candidate):
            out.append(c)
        elif isinstance(c, Target):
            out.append(Candidate.of(c))
        elif isinstance(c, str):
            out.append(Candidate.of(as_target(c)))
        else:
            raise TypeError(f"space entries must be Candidate, Target or "
                            f"backend string; got {type(c).__name__}")
    return out


def _rank_correlation(results: Sequence[CandidateResult]) -> float | None:
    """Spearman rank correlation between predicted and measured seconds
    over the measured set (``None`` with <2 scored points or a
    degenerate ranking)."""
    pts = [(r.predicted_s, r.median_s) for r in results
           if r.predicted_s is not None]
    if len(pts) < 2:
        return None
    pred = np.asarray([p for p, _ in pts], dtype=float)
    meas = np.asarray([m for _, m in pts], dtype=float)
    rp = np.argsort(np.argsort(pred)).astype(float)
    rm = np.argsort(np.argsort(meas)).astype(float)
    if rp.std() == 0 or rm.std() == 0:
        return None
    return float(np.corrcoef(rp, rm)[0, 1])


def _default_scorer(program_or_spec, is_program: bool, grid, *,
                    lattice, halo, consts,
                    profile) -> Callable[[Target], float | None]:
    """The costmodel-backed candidate scorer: plan the subject under the
    candidate target, :func:`repro.core.costmodel.predict` the plan.
    ``profile=None`` resolves per candidate (interpret candidates score
    against the interpret profile, compiled ones against the compiled
    profile — the honest-profile rule).  Returns ``None`` for
    candidates the model cannot score."""

    def scorer(tgt: Target) -> float | None:
        try:
            if is_program:
                plan = program_or_spec.plan(
                    tgt.with_(mesh=None, shard_axis=None),
                    grid_shape=grid)
            else:
                plan = _launch_plan(program_or_spec, tgt, lattice=lattice,
                                    halo=halo, consts=consts)
            return float(_costmodel.predict(plan, profile=profile).seconds)
        except Exception:
            return None

    return scorer


def autotune(program_or_spec, target: Target | str | None = None,
             example_state=None, *,
             space: Sequence | None = None,
             budget: int | None = None,
             measure_steps: int = 3,
             reps: int = 3, warmup: int = 1,
             timer: Callable[[Target, Callable[[], Any]], float] | None
             = None,
             grid_shape: Sequence[int] | None = None,
             lattice: Lattice | None = None, halo=None, consts=None,
             executors: Sequence[str] | None = None,
             vmem_limit: int | None = None,
             check_outputs: bool = False,
             scorer: Callable[[Target], float | None] | None = None,
             top_k: int | None = None,
             profile=None,
             per_stage: bool = False,
             cache_dir: str | None = "results/tuning") -> TuneResult:
    """Choose ``Target.tuning`` (and the executor) empirically.

    Args:
      program_or_spec: a :class:`Program`, :class:`CompiledProgram` (its
        program/target/grid are reused), or :class:`KernelSpec`.
      target: the base target — always measured as candidate 0, so the
        returned target's median is ≤ the default-tuning median.  For a
        ``CompiledProgram``, defaults to its compile target.
      example_state: what one measurement runs on — a ``{field: (ncomp,
        *grid)}`` mapping for programs, a sequence of ``(ncomp, nsites)``
        SoA arrays for specs.
      space: explicit candidate list (:class:`Candidate` / ``Target`` /
        backend strings); ``None`` derives :func:`default_space`.
      budget: measure at most this many candidates (the base target is
        always kept; the rest are taken in space order).
      measure_steps: steps per timed call — ``Program`` candidates run
        ``measure_steps`` compiled steps per sample, specs launch
        ``measure_steps`` times.
      reps / warmup: samples per candidate (median taken) / discarded
        leading calls (compile + cache warm).
      timer: ``(candidate_target, run) -> seconds``; default
        :func:`wall_clock_timer`.  Inject a fake for deterministic tests.
      grid_shape / lattice / halo / consts: launch geometry (programs
        infer ``grid_shape`` from ``example_state``).
      executors / vmem_limit: forwarded to :func:`default_space`.
      check_outputs: additionally run every candidate once and prune
        any whose outputs fail :func:`~repro.core.state.check_fields`
        against the base target's (tuning must never change results
        beyond rounding; a mismatch is an executor bug, surfaced in
        ``report.pruned`` with the field and its gap, never silently
        chosen).
      scorer: ``(candidate_target) -> predicted seconds | None`` — the
        analytical model ranking the space.  Defaults to the
        :mod:`repro.core.costmodel` roofline predictor.  Every measured
        candidate records its prediction (``predicted_s``,
        ``predicted_vs_measured``) and the report the Spearman
        ``rank_correlation`` over the measured set.
      top_k: measure only the base target plus the ``top_k``
        best-predicted candidates (at most ``top_k + 1`` measurements);
        the rest land in ``report.pruned`` with a ``model-pruned``
        reason — recorded, never silently dropped.  Candidate 0 (the
        base target) is always measured regardless of its score.
      profile: the :class:`repro.core.costmodel.MachineProfile` for the
        default scorer (``None`` resolves per candidate — interpret
        candidates against the interpret profile).
      per_stage: also sweep per-stage ``plane_block`` assignments for
        programs with more than one windowed stage (the reserved
        ``"stage:<name>"`` tuning keys; see :func:`default_space`).
      cache_dir: on-disk cache directory (``None`` disables).  A hit
        replays the stored choice without measuring.

    Returns a :class:`TuneResult` ``(tuned_target, report)``.
    """
    if isinstance(program_or_spec, CompiledProgram):
        if target is None:
            target = program_or_spec.target
        if grid_shape is None:
            grid_shape = program_or_spec.grid_shape
        program_or_spec = program_or_spec.program
    base = as_target(target)

    is_program = isinstance(program_or_spec, Program)
    if is_program:
        if example_state is None:
            raise ValueError("autotune over a Program needs example_state "
                             "({field: (ncomp, *grid) array})")
        state = {f: example_state[f] for f in program_or_spec.fields}
        if grid_shape is None:
            grid_shape = tuple(
                int(s) for s in next(iter(state.values())).shape[1:])
        grid = tuple(int(s) for s in grid_shape)
    elif isinstance(program_or_spec, KernelSpec):
        if example_state is None:
            raise ValueError("autotune over a KernelSpec needs "
                             "example_state (the launch arrays)")
        arrays = tuple(example_state)
        if program_or_spec.has_stencil and lattice is None:
            raise ValueError("autotune over a stencil KernelSpec needs "
                             "the lattice")
        grid = (tuple(lattice.shape) if lattice is not None
                else (int(arrays[0].shape[-1]),))
    else:
        raise TypeError(f"autotune expects a Program, CompiledProgram or "
                        f"KernelSpec; got {type(program_or_spec).__name__}")

    key = cache_key(program_or_spec, base, grid)
    if cache_dir is not None:
        cached = load_cached(cache_dir, key)
        if cached is not None:
            return TuneResult(cached.best.target_from(base), cached)

    if space is None:
        candidates, pruned = default_space(
            program_or_spec, base, grid_shape=grid if is_program else None,
            lattice=lattice, halo=halo, consts=consts,
            executors=executors, vmem_limit=vmem_limit,
            per_stage=per_stage,
            site_count=None if is_program else int(arrays[0].shape[-1]))
    else:
        pruned = []
        base_cand = Candidate.of(base)
        # the base target is always candidate 0 (the default-median
        # baseline, the check_outputs reference, the must-run entry) —
        # even when an explicit space lists it elsewhere
        candidates = [base_cand] + [c for c in _as_candidates(space)
                                    if c != base_cand]
    if budget is not None and len(candidates) > max(1, int(budget)):
        kept = candidates[:max(1, int(budget))]
        for c in candidates[len(kept):]:
            pruned.append((c.label, f"over budget={budget}"))
        candidates = kept

    # -- predictor pass: score every candidate (the predictions annotate
    # every cache entry even without top_k; an unscoreable candidate is
    # None, never an error) --------------------------------------------
    if scorer is None:
        scorer = _default_scorer(program_or_spec, is_program, grid,
                                 lattice=lattice, halo=halo,
                                 consts=consts, profile=profile)
    scores: dict[str, float | None] = {}
    for c in candidates:
        try:
            s = scorer(c.target_from(base))
        except Exception:  # noqa: BLE001 — a scorer failure never blocks
            s = None
        scores[c.label] = None if s is None else float(s)

    if top_k is not None:
        k = max(0, int(top_k))
        rest = candidates[1:]       # candidate 0 is never model-pruned
        ranked = sorted((c for c in rest if scores[c.label] is not None),
                        key=lambda c: scores[c.label])
        keep = {c.label for c in ranked[:k]}
        for rank, c in enumerate(ranked[k:], start=k + 1):
            pruned.append(
                (c.label, f"model-pruned: predicted rank {rank} > "
                          f"top_k={k} ({scores[c.label]:.3g}s)"))
        for c in rest:
            if scores[c.label] is None:
                pruned.append((c.label, "model-pruned: scorer returned "
                                        "no estimate"))
        candidates = [candidates[0]] + [c for c in rest
                                        if c.label in keep]

    timer = timer if timer is not None else wall_clock_timer
    n_steps = max(1, int(measure_steps))

    def runner(tgt: Target) -> Callable[[], Any]:
        if is_program:
            exe = program_or_spec.compile(
                tgt.with_(mesh=None, shard_axis=None), grid_shape=grid)
            return lambda: exe.run(state, n_steps)

        def run():
            out = None
            for _ in range(n_steps):
                out = _launch(program_or_spec, tgt, *arrays,
                              lattice=lattice, halo=halo,
                              consts=dict(consts or {}))
            return out
        return run

    ref_out = None
    results: list[CandidateResult] = []
    pruned = list(pruned)
    default_median = None
    for i, cand in enumerate(candidates):
        tgt = cand.target_from(base)
        try:
            run = runner(tgt)
            if check_outputs:
                out = run()
                out = out if isinstance(out, (Mapping, tuple)) else (out,)
                if i == 0:
                    ref_out = out
                else:
                    try:
                        check_fields(ref_out, out)
                    except AssertionError as e:
                        pruned.append((cand.label, f"output differs from "
                                                   f"the default target: {e}"))
                        continue
            for _ in range(max(0, int(warmup))):
                timer(tgt, run)
            times = tuple(float(timer(tgt, run))
                          for _ in range(max(1, int(reps))))
        except Exception as e:  # noqa: BLE001 — an unrunnable candidate
            # (e.g. real-Pallas on a CPU host) is pruned, not fatal...
            if i == 0:
                raise   # ...but the *base* target must be runnable.
            pruned.append((cand.label, f"error: {type(e).__name__}: {e}"))
            continue
        median = float(np.median(times))
        if i == 0:
            default_median = median
        predicted = scores.get(cand.label)
        pvm = ((predicted - median) / median
               if predicted is not None and median > 0 else None)
        results.append(CandidateResult(cand, median, times, predicted,
                                       pvm))

    if not results:
        raise RuntimeError(
            f"autotune({key}): no candidate survived measurement "
            f"(pruned: {[p[0] for p in pruned]})")
    # min() keeps the *first* minimum, and the base target is always
    # measured first — exact ties go to candidate 0, so a tuned target
    # never trades the default dispatch for an equally-fast exotic one
    best = min(results, key=lambda r: r.median_s).candidate
    report = TuneReport(
        name=_subject_digest(program_or_spec)[0], grid=grid,
        device=_costmodel._device_kind(), results=tuple(results),
        pruned=tuple(pruned), best=best,
        default_median_s=float(default_median),
        cache_key=key, cache_hit=False, measure_steps=n_steps,
        rank_correlation=_rank_correlation(results))
    if cache_dir is not None:
        store_cached(cache_dir, report)
    return TuneResult(best.target_from(base), report)
