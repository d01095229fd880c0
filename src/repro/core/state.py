"""``ProgramState`` — the named-field pytree container for Program state.

A :class:`~repro.core.program.Program` steps a set of named fields
(``{"f": (19, X, Y, Z), "g": (19, X, Y, Z)}``).  Until now that state was
a plain dict; fleets (:mod:`repro.core.fleet`) need a container that
additionally *annotates* what the leading axis means — the annotated-
pytree idiom: the pytree leaves are the field arrays, the aux data
carries the field names **and** whether an ensemble axis is present.

* ``ProgramState({"f": f, "g": g})`` — single-member state; every field
  is ``(ncomp, *grid_shape)``.
* ``ProgramState({...}, ensemble=B)`` — fleet state; every field is
  ``(B, ncomp, *grid_shape)`` (ensemble axis **leading**, so ``vmap``
  over axis 0 lifts a compiled step to the whole ensemble).
* ``ProgramState.stack([s0, s1, ...])`` ↔ ``state.unstack()`` /
  ``state.member(i)`` move between the two.

``CompiledProgram.step``/``run`` accept either a plain mapping or a
``ProgramState`` and return the same kind; ``FleetProgram`` requires the
ensemble form (or a mapping of pre-batched arrays).  Validation
(:meth:`ProgramState.validate` / :func:`validate_field`) names the
offending field and dimension instead of dumping bare shape tuples.

:func:`check_fields` is the one contract for comparing the fields two
execution paths produce (see its docstring and ``docs/targetdp_api.md``).
"""
from __future__ import annotations

from collections.abc import Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np


def _dim_name(i: int, ensemble: bool) -> str:
    if ensemble and i == 0:
        return "dim 0 (ensemble)"
    j = i - (1 if ensemble else 0)
    return ("dim %d (ncomp)" % i) if j == 0 else (
        "dim %d (grid dim %d)" % (i, j - 1))


def validate_field(name: str, arr, *, ncomp: int | None,
                   grid_shape: tuple[int, ...],
                   ensemble: int | None = None,
                   program: str | None = None) -> None:
    """Shape/ncomp check for one field, raising errors that name the
    offending field and dimension.

    Expected shape: ``(ncomp, *grid_shape)``, with a leading ``ensemble``
    extent prepended when given.  ``ncomp=None`` skips the component
    check (the Program could not infer it).
    """
    where = f" of program {program!r}" if program else ""
    exp = ((ensemble,) if ensemble is not None else ()) \
        + (ncomp if ncomp is not None else -1,) + tuple(grid_shape)
    rank = len(exp)
    got = getattr(arr, "shape", None)
    if got is None or getattr(arr, "ndim", None) != rank:
        raise ValueError(
            f"field {name!r}{where} must be rank {rank} "
            f"({'ensemble, ' if ensemble is not None else ''}ncomp, "
            f"{', '.join(map(str, grid_shape))}); got "
            f"{'rank ' + str(arr.ndim) if hasattr(arr, 'ndim') else 'a non-array'}"
            f" with shape {got}")
    off = 1 if ensemble is not None else 0
    if ensemble is not None and int(got[0]) != ensemble:
        raise ValueError(
            f"field {name!r}{where}: {_dim_name(0, True)} is {got[0]}, "
            f"expected ensemble extent {ensemble}")
    if ncomp is not None and int(got[off]) != ncomp:
        raise ValueError(
            f"field {name!r}{where}: {_dim_name(off, ensemble is not None)} "
            f"is {got[off]}, expected ncomp {ncomp}")
    for d, want in enumerate(grid_shape):
        i = off + 1 + d
        if int(got[i]) != int(want):
            raise ValueError(
                f"field {name!r}{where}: "
                f"{_dim_name(i, ensemble is not None)} is {got[i]}, "
                f"expected grid extent {want} "
                f"(grid_shape {tuple(grid_shape)})")


#: Largest gap :func:`check_fields` allows per field, for values of unit
#: scale: ``"f"`` and ``"g"`` are the LB populations, ``None`` any other
#: field.  How the values were set is in :func:`check_fields`.
FIELD_ATOL: Mapping[str | None, float] = {"f": 1e-6, "g": 1e-6, None: 1e-6}


def check_fields(want, got) -> None:
    """Hold the fields ``got`` to ``want`` under the cross-path contract.

    Two runs of the same steps compare bitwise only when both run the
    same stage launches on the same executor, layout, fusion mode,
    decomposition, ``plane_block`` and ``y_block``, and only the host's
    driving differs (a step loop against ``run``'s scan, donation, a
    guarded or chunked run, fleet replay, checkpoint restore).  Any other
    pair -- another executor, fusion mode, layout, vvl, decomposition or
    blocking, or a plain reference -- is held to :data:`FIELD_ATOL`: the
    same arithmetic in another order or another fusion rounds the last
    bits of a value differently on each host, while a wrong neighbour or
    a wrong weight moves it by far more.

    ``want`` and ``got`` map field names to arrays (a sequence names its
    entries by position and takes the generic tolerance).  A field's
    tolerance scales with its largest expected magnitude where that
    exceeds 1 (the populations stay below it, so theirs is absolute).
    NaN on both sides at a site agrees; on one side it is a gap of
    ``inf``.  Raises ``AssertionError`` naming the first field over its
    tolerance, its largest gap and the index where that gap is.

    The tolerances (1e-6 for f, g and any other field) rest on the
    largest gap of every cross-path pair the tests run, at their sizes
    and step counts, on the CPU (x86-64, XLA's CPU backend, Pallas in
    interpret mode): 8.94e-8 in f and 8.94e-8 in g (10 steps at 16³:
    unfused against fused, windowed against xla, local against pencil
    and block, the pre-Program driver, autotune candidates, and every
    path against the benchmark's plain reference), 0 in every other
    field.  Each is at least 10× its gap and below the chip benchmark's
    limits (``max_abs_df`` 5e-6, ``max_abs_dg`` 1e-3); a population
    streamed one site too far moves f by 7.2e-5 in 10 steps.
    """
    want, got = _named(want), _named(got)
    if set(want) != set(got):
        raise AssertionError(f"fields differ: expected {sorted(want)}, "
                             f"got {sorted(got)}")
    for name in want:
        a = np.asarray(want[name], np.float64)
        b = np.asarray(got[name], np.float64)
        if a.shape != b.shape:
            raise AssertionError(f"field {name!r}: shape {b.shape}, "
                                 f"expected {a.shape}")
        if not a.size:
            continue
        with np.errstate(invalid="ignore"):
            gap = np.where((a == b) | (np.isnan(a) & np.isnan(b)), 0.0,
                           np.nan_to_num(np.abs(a - b), nan=np.inf))
        i = np.unravel_index(int(np.argmax(gap)), gap.shape)
        finite = np.abs(a[np.isfinite(a)])
        scale = max(1.0, float(finite.max())) if finite.size else 1.0
        tol = FIELD_ATOL.get(name, FIELD_ATOL[None]) * scale
        if gap[i] > tol:
            raise AssertionError(
                f"field {name!r}: largest gap {gap[i]:.3g} at index "
                f"{tuple(int(k) for k in i)} (got {b[i]:.9g}, expected "
                f"{a[i]:.9g}) exceeds its tolerance {tol:.3g}")


def _named(fields) -> dict:
    if isinstance(fields, Mapping):
        return dict(fields)
    return {str(i): x for i, x in enumerate(fields)}


@jax.tree_util.register_pytree_node_class
class ProgramState(Mapping):
    """Registered-pytree mapping of field name → array, annotated with an
    optional leading ensemble axis.

    Behaves as a read-only mapping (``state["f"]``, ``dict(state)``,
    ``**state``); the pytree leaves are the arrays in field order, the
    aux data is ``(names, ensemble)`` — so ``jax.vmap``/``lax.scan``/
    checkpointing treat it structurally and the annotation survives
    tracing.
    """

    __slots__ = ("_names", "_arrays", "ensemble")

    def __init__(self, arrays: Mapping[str, jax.Array], *,
                 ensemble: int | None = None):
        if not isinstance(arrays, Mapping):
            raise TypeError(f"ProgramState expects a mapping of field "
                            f"name -> array, got {type(arrays).__name__}")
        if ensemble is not None and int(ensemble) <= 0:
            raise ValueError(f"ensemble extent must be positive, "
                             f"got {ensemble}")
        self._names = tuple(arrays)
        self._arrays = {str(k): arrays[k] for k in self._names}
        self.ensemble = int(ensemble) if ensemble is not None else None

    # -- mapping protocol --------------------------------------------------

    def __getitem__(self, key: str):
        try:
            return self._arrays[key]
        except KeyError:
            raise KeyError(
                f"ProgramState has no field {key!r}; fields: "
                f"{list(self._names)}") from None

    def __iter__(self) -> Iterator[str]:
        return iter(self._names)

    def __len__(self) -> int:
        return len(self._names)

    @property
    def fields(self) -> tuple[str, ...]:
        return self._names

    def replace(self, **arrays) -> "ProgramState":
        """Copy with the named field arrays swapped."""
        unknown = sorted(set(arrays) - set(self._names))
        if unknown:
            raise ValueError(f"ProgramState.replace: unknown field(s) "
                             f"{unknown}; fields: {list(self._names)}")
        return ProgramState({n: arrays.get(n, self._arrays[n])
                             for n in self._names}, ensemble=self.ensemble)

    # -- ensemble axis -----------------------------------------------------

    @classmethod
    def stack(cls, states) -> "ProgramState":
        """Stack single-member states (mappings or ``ProgramState``\\ s)
        into one ensemble state along a new leading axis."""
        states = list(states)
        if not states:
            raise ValueError("ProgramState.stack needs at least one state")
        names = tuple(states[0])
        for i, s in enumerate(states):
            if tuple(s) != names:
                raise ValueError(
                    f"ProgramState.stack: member {i} has fields "
                    f"{list(s)}, expected {list(names)}")
            if isinstance(s, ProgramState) and s.ensemble is not None:
                raise ValueError(
                    f"ProgramState.stack: member {i} already carries an "
                    f"ensemble axis (ensemble={s.ensemble})")
        return cls({n: jnp.stack([s[n] for s in states]) for n in names},
                   ensemble=len(states))

    def member(self, i: int) -> "ProgramState":
        """Member *i* of an ensemble state (drops the ensemble axis)."""
        if self.ensemble is None:
            raise ValueError("ProgramState.member: state has no ensemble "
                             "axis")
        if not (-self.ensemble <= int(i) < self.ensemble):
            raise IndexError(f"member {i} out of range for ensemble "
                             f"extent {self.ensemble}")
        return ProgramState({n: self._arrays[n][i] for n in self._names})

    def unstack(self) -> list["ProgramState"]:
        """Split an ensemble state into its members."""
        if self.ensemble is None:
            raise ValueError("ProgramState.unstack: state has no ensemble "
                             "axis")
        return [self.member(i) for i in range(self.ensemble)]

    # -- validation --------------------------------------------------------

    def validate(self, ncomp: Mapping[str, int | None],
                 grid_shape, *, fields=None,
                 program: str | None = None) -> None:
        """Check every field's shape against ``(ncomp, *grid_shape)``
        (plus this state's ensemble extent, if any), raising errors that
        name the offending field and dim.  ``fields`` defaults to this
        state's own field set."""
        grid_shape = tuple(int(s) for s in grid_shape)
        for f in (fields if fields is not None else self._names):
            if f not in self._arrays:
                raise ValueError(
                    f"state{' for program ' + repr(program) if program else ''}"
                    f" is missing field {f!r}; present: "
                    f"{list(self._names)}")
            validate_field(f, self._arrays[f], ncomp=ncomp.get(f),
                           grid_shape=grid_shape, ensemble=self.ensemble,
                           program=program)

    # -- pytree protocol ---------------------------------------------------

    def tree_flatten(self):
        return (tuple(self._arrays[n] for n in self._names),
                (self._names, self.ensemble))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        names, ensemble = aux
        obj = cls.__new__(cls)
        obj._names = names
        obj._arrays = dict(zip(names, leaves))
        obj.ensemble = ensemble
        return obj

    def __repr__(self):
        shapes = {n: tuple(getattr(a, "shape", ()))
                  for n, a in self._arrays.items()}
        ens = f", ensemble={self.ensemble}" if self.ensemble is not None \
            else ""
        return f"ProgramState({shapes}{ens})"
