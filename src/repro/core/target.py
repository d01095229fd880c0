"""Target descriptors — *where and how* a kernel launch executes.

The paper selects its implementation (C vs CUDA) with a build switch; the
successor paper (1609.01479) and Alpaka (1602.08477) make the target an
exchangeable *descriptor* instead.  :class:`Target` is that descriptor: a
small frozen value object naming the executor, carrying the tunable VVL
(ILP extent), the interpret flag (Pallas semantics on CPU), optional
mesh/sharding hints, and an executor-specific ``tuning`` mapping for
per-op knobs (block sizes etc.) that used to be threaded by hand.

Being frozen and hashable, a Target participates directly in the launch
plan cache key — two launches under different targets can never alias one
compiled closure (the ``set_default_vvl`` staleness class of bug).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Mapping

# Default VVL: one full TPU vector register row of lanes.  The paper tunes
# VVL per architecture (8 on AVX, 2 on K40); tdp.autotune sweeps it.
_DEFAULT_VVL = 128


def default_vvl() -> int:
    return _DEFAULT_VVL


def set_default_vvl(vvl: int) -> None:
    """Change the process-wide default VVL.

    Targets with ``vvl=None`` resolve this value *at launch time*, and the
    resolved VVL is part of the plan cache key — so flipping the default
    between two launches always rebuilds the closure (regression-pinned by
    ``tests/test_tdp_api.py``).
    """
    global _DEFAULT_VVL
    if vvl <= 0:
        raise ValueError("vvl must be positive")
    _DEFAULT_VVL = int(vvl)


def _freeze_tuning(tuning) -> tuple[tuple[str, Any], ...]:
    if isinstance(tuning, Mapping):
        items = sorted(tuning.items())
    else:
        items = sorted(tuple(kv) for kv in tuning)
    for k, v in items:
        if not isinstance(k, str):
            raise TypeError(f"tuning keys must be strings, got {k!r}")
        hash(v)  # tuning participates in the plan cache key
    return tuple((k, v) for k, v in items)


@dataclass(frozen=True)
class Target:
    """Execution target descriptor (replaces the stringly ``backend=`` +
    ``vvl=`` kwarg plumbing).

    Args:
      backend: executor name in the registry (``"xla"``, ``"pallas"``,
        ``"pallas_windowed"``, or any
        :func:`repro.core.register_executor`-registered name).  The
        spellings ``"pallas_interpret"`` / ``"pallas_windowed_interpret"``
        canonicalise to the base backend + ``interpret=True``.
      vvl: virtual vector length (ILP extent).  ``None`` → resolve the
        process default at launch time.  (The windowed executor chunks by
        x-planes, not VVL — see ``plane_block`` below.)
      interpret: run Pallas semantics on CPU (validation mode).
      layout: executor-internal memory layout — ``"soa"`` (default;
        sites contiguous per component) or ``"aosoa"`` (vvl-site blocks
        outermost, the paper's AoSoA ``VVL`` ordering; ``vvl`` is the
        inner block width).  Callers always pass and receive SoA
        ``(ncomp, nsites)`` arrays; the transforms live at field
        boundaries (:mod:`repro.core.layout`), so kernels stay
        single-source and results agree across layouts under the
        cross-path contract (:func:`repro.core.state.check_fields`).
      mesh / shard_axis: optional sharding hints for mesh-aware callers
        (e.g. :class:`repro.lb.sim.BinaryFluidSim`); the core launch does
        not act on them, it only carries them.  ``shard_axis`` is one
        mesh-axis name (slab decomposition) or a tuple of names
        (pencil/block: axis *k* shards grid dim *k*).
      tuning: executor/op-specific knobs, stored as a sorted tuple of
        pairs so the Target stays hashable.  Established keys:
        ``block_f`` / ``block_q`` / ... (pointwise Pallas block sizes,
        see :mod:`repro.kernels.ops`) and ``plane_block`` (the
        ``pallas_windowed`` executor's TLP chunk: how many output
        x-planes each grid step computes; its VMEM window depth is
        ``plane_block + 2·radius`` planes).
    """

    backend: str = "xla"
    vvl: int | None = None
    interpret: bool = False
    layout: str = "soa"
    mesh: Any = None
    shard_axis: str | tuple[str, ...] | None = None
    tuning: tuple[tuple[str, Any], ...] = field(default=())

    def __post_init__(self):
        if not isinstance(self.backend, str) or not self.backend:
            raise ValueError(f"backend must be a non-empty string, got "
                             f"{self.backend!r}")
        if self.backend in ("pallas_interpret", "pallas_windowed_interpret"):
            object.__setattr__(self, "backend",
                               self.backend[:-len("_interpret")])
            object.__setattr__(self, "interpret", True)
        if self.vvl is not None:
            if int(self.vvl) <= 0:
                raise ValueError(f"vvl must be positive, got {self.vvl}")
            object.__setattr__(self, "vvl", int(self.vvl))
        if self.layout not in ("soa", "aosoa"):
            raise ValueError(
                f"layout must be 'soa' or 'aosoa', got {self.layout!r} "
                f"(the AoSoA inner width is the separate vvl field)")
        # multi-axis decompositions name one mesh axis per sharded grid
        # dim; freeze to a tuple so the Target stays hashable
        if isinstance(self.shard_axis, (list, tuple)):
            object.__setattr__(self, "shard_axis",
                               tuple(str(a) for a in self.shard_axis))
        object.__setattr__(self, "tuning", _freeze_tuning(self.tuning))

    @property
    def executor(self) -> str:
        """Registry name this target dispatches to."""
        if self.backend == "pallas" and self.interpret:
            return "pallas_interpret"
        return self.backend

    def resolve_vvl(self) -> int:
        """The VVL this target launches with *right now* (explicit value,
        else the current process default)."""
        return self.vvl if self.vvl is not None else _DEFAULT_VVL

    def tuning_dict(self) -> dict[str, Any]:
        return dict(self.tuning)

    def tune(self, key: str, default: Any = None) -> Any:
        for k, v in self.tuning:
            if k == key:
                return v
        return default

    def with_(self, **updates) -> "Target":
        """Functional update (``dataclasses.replace`` with dict-friendly
        ``tuning``)."""
        if "tuning" in updates:
            updates["tuning"] = _freeze_tuning(updates["tuning"])
        return dataclasses.replace(self, **updates)

    # ``with_`` under its conventional name, for callers that expect the
    # dataclasses spelling (tdp.autotune's report records use it).
    replace = with_

    def with_tuning(self, updates: "Mapping[str, Any] | None" = None,
                    **kw) -> "Target":
        """Merge tuning knobs into the existing ``tuning`` mapping.

        Unlike ``with_(tuning=...)`` — which *replaces* the whole mapping
        — this keeps unrelated knobs: ``t.with_tuning(plane_block=2)`` on
        a target already carrying ``block_f`` preserves ``block_f``.  The
        result re-freezes (sorted, hashable), so equal merged tunings
        always compare and hash equal regardless of update order — the
        plan-cache-key contract ``tdp.autotune`` candidates rely on.
        """
        merged = self.tuning_dict()
        if updates:
            merged.update(updates)
        merged.update(kw)
        return self.with_(tuning=merged)


def as_target(target: "Target | str | None" = None, *,
              vvl: int | None = None,
              layout: str | None = None) -> Target:
    """Coerce the accepted spellings to a :class:`Target`.

    This is the *single* place a backend string becomes a Target — ops and
    launches accept strings only through here.

    ``None`` → default xla target; a string → ``Target(backend=string)``;
    a Target passes through.  ``vvl`` / ``layout`` (if given) override
    the target's.
    """
    if target is None:
        target = Target()
    elif isinstance(target, str):
        target = Target(backend=target)
    elif not isinstance(target, Target):
        raise TypeError(
            f"expected a Target, backend-name string, or None; got "
            f"{type(target).__name__}: {target!r}")
    if vvl is not None:
        target = target.with_(vvl=vvl)
    if layout is not None:
        target = target.with_(layout=layout)
    return target
