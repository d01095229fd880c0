"""Executor registry — the pluggable backend table behind ``tdp.launch``.

An *executor* realises the paper's ``TARGET_TLP``/``TARGET_ILP`` loops for
one architecture.  The core launch path (validation, padding, const
unwrapping, the neighbour prologue, plan caching) is executor-independent;
an executor only maps a prepared plan over prepared site arrays:

    def my_executor(plan, prepared):
        # plan:     repro.core.api.LaunchPlan (kernel, vvl, out_ncomp,
        #           consts, with_site_index, interpret, target, shape,
        #           halo, stencils, wants, memory estimates)
        # prepared: one array per input field.  What a stencil field looks
        #           like depends on the executor's declared capability:
        #             wants="gathered"       (default) — the shared gather
        #               prologue ran: (noffsets, ncomp, nsites) neighbour
        #               stack per stencil field, (ncomp, nsites) pointwise.
        #             wants="halo_extended"  — no gather: each stencil
        #               field arrives ONCE as a halo-extended grid
        #               (ncomp, *ext_shape) with exactly
        #               stencil.radius_per_dim() ghost layers per
        #               dimension (periodic dims wrap-padded, sharded
        #               dims trimmed from the caller's ghost planes);
        #               the executor resolves offsets itself, in-kernel.
        #               With wraps_periodic=True the periodic dims are
        #               not padded either: they arrive at their interior
        #               extent and the executor wraps them itself.
        #               With blocks_y=True the plan may also carry a
        #               y_block: the executor then windows y in blocks
        #               of that many rows (LaunchPlan.y_block).
        # returns:  tuple of (ncomp_o, nsites) outputs, one per
        #           plan.out_ncomp entry (a bare array is accepted for
        #           single-output kernels)
        ...

    register_executor("my_backend", my_executor)                 # gathered
    register_executor("my_windowed", my_win, wants="halo_extended")
    register_executor("my_wrapping", my_wrap, wants="halo_extended",
                      wraps_periodic=True)
    tdp.launch(spec, Target("my_backend"), *arrays)

Registering a new architecture is *one* ``register_executor`` call — the
windowed-block stencil executor (``"pallas_windowed"``) lands this way,
not as a fork of launch logic.  Registration bumps an internal version
that is part of the plan cache key, so re-registering a name (even with a
different capability) can never serve a stale compiled closure.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

#: Executor input capabilities: what the launch prologue prepares for each
#: stencil-carrying field before dispatch.
EXECUTOR_WANTS = ("gathered", "halo_extended")


class ExecutorEntry(NamedTuple):
    """One registry row: the executor callable plus its declared input
    capability (see ``EXECUTOR_WANTS``), the ``Target.tuning`` keys it
    consults (``tunables`` — the sweep/autotune surface), whether it
    wraps periodic dimensions itself (``wraps_periodic``) and whether it
    runs plans whose window is blocked in y (``blocks_y``)."""

    fn: Callable
    wants: str
    tunables: tuple[str, ...] = ()
    wraps_periodic: bool = False
    blocks_y: bool = False


_EXECUTORS: dict[str, ExecutorEntry] = {}
_VERSION = 0


def register_executor(name: str, fn: Callable, *, overwrite: bool = False,
                      wants: str = "gathered",
                      tunables: tuple[str, ...] = (),
                      wraps_periodic: bool = False,
                      blocks_y: bool = False) -> None:
    """Register ``fn`` as the executor behind ``Target(backend=name)``.

    ``wants`` declares the input capability: ``"gathered"`` (default)
    receives pre-gathered ``(noffsets, ncomp, nsites)`` neighbour stacks;
    ``"halo_extended"`` suppresses the gather and receives each stencil
    field once, as a halo-extended ``(ncomp, *ext_shape)`` grid.

    ``tunables`` declares the ``Target.tuning`` keys the executor actually
    consults (e.g. ``("plane_block",)`` for the windowed executor) — the
    contract ``tdp.autotune`` builds candidate spaces from; sweeping a
    key outside this set is rejected up front instead of silently
    measuring a no-op.

    ``wraps_periodic`` (``"halo_extended"`` executors only) declares that
    the executor wraps periodic dimensions (launch ``halo[d] == 0``)
    itself: the prologue then pads nothing there, and each stencil field
    arrives at its interior extent in those dimensions — on a fully
    periodic lattice as the plain ``(ncomp, *shape)`` reshape, with no
    copy.  Dimensions with caller ghosts keep the ``halo_extended``
    contract (ghost planes trimmed to the stencil radius).  The plan
    records the wrapped dimensions per field (``LaunchPlan.wrap_dims``).

    ``blocks_y`` (``"halo_extended"`` executors only) declares that the
    executor runs plans whose y-z window is cut into blocks of
    ``LaunchPlan.y_block`` rows: plan build then picks the block
    from the VMEM estimate against the device's limit whenever the
    whole-plane window does not fit.  Without it ``y_block`` stays
    ``None`` and such a launch is refused (``WindowVmemError``).

    Raises ``ValueError`` on duplicate names unless ``overwrite=True``.
    """
    global _VERSION
    if not isinstance(name, str) or not name:
        raise ValueError(f"executor name must be a non-empty string, "
                         f"got {name!r}")
    if not callable(fn):
        raise TypeError(f"executor must be callable, got {fn!r}")
    if wants not in EXECUTOR_WANTS:
        raise ValueError(f"executor capability must be one of "
                         f"{EXECUTOR_WANTS}, got {wants!r}")
    if wraps_periodic and wants != "halo_extended":
        raise ValueError(f"wraps_periodic needs wants='halo_extended' (a "
                         f"gathered executor receives no grid to wrap), "
                         f"got wants={wants!r}")
    if blocks_y and wants != "halo_extended":
        raise ValueError(f"blocks_y needs wants='halo_extended' (a "
                         f"gathered executor has no window to block), "
                         f"got wants={wants!r}")
    tunables = tuple(str(t) for t in tunables)
    if name in _EXECUTORS and not overwrite:
        raise ValueError(
            f"executor {name!r} is already registered; pass overwrite=True "
            f"to replace it")
    _EXECUTORS[name] = ExecutorEntry(fn, wants, tunables,
                                     bool(wraps_periodic), bool(blocks_y))
    _VERSION += 1


def unregister_executor(name: str) -> None:
    global _VERSION
    if name not in _EXECUTORS:
        raise ValueError(f"executor {name!r} is not registered "
                         f"(have: {sorted(_EXECUTORS)})")
    del _EXECUTORS[name]
    _VERSION += 1


def get_executor(name: str) -> Callable:
    return get_executor_entry(name).fn


def get_executor_entry(name: str) -> ExecutorEntry:
    """The full registry row — callable plus declared capability."""
    try:
        return _EXECUTORS[name]
    except KeyError:
        raise ValueError(
            f"unknown executor {name!r}; registered executors: "
            f"{sorted(_EXECUTORS)}") from None


def executor_wants(name: str) -> str:
    """The declared input capability of a registered executor."""
    return get_executor_entry(name).wants


def executor_tunables(name: str) -> tuple[str, ...]:
    """The ``Target.tuning`` keys a registered executor consults."""
    return get_executor_entry(name).tunables


def compatible_executors(*, stencil: bool) -> tuple[str, ...]:
    """Registered executor names able to run a launch of the given shape.

    A stencil-carrying spec can run on every capability (the prologue
    adapts: gather vs halo-extend); a pure pointwise spec has nothing to
    window, so ``wants="halo_extended"`` executors are excluded — the
    same rule :func:`repro.core.api.launch` enforces at dispatch.  This
    is the executor axis of ``tdp.autotune``'s candidate space.
    """
    return tuple(sorted(
        name for name, entry in _EXECUTORS.items()
        if stencil or entry.wants != "halo_extended"))


def list_executors() -> tuple[str, ...]:
    return tuple(sorted(_EXECUTORS))


def registry_version() -> int:
    """Monotonic counter bumped on every (un)registration — part of the
    launch-plan cache key."""
    return _VERSION
