"""``tdp.Program`` — declarative multi-launch step graphs.

The paper's targetDP layer abstracts *single* kernel launches; a real
lattice application step (the Ludwig binary fluid, our
:class:`repro.lb.sim.BinaryFluidSim`) is a short *pipeline* of launches
plus host-side glue: halo exchange, executor fallbacks, intermediate
buffers, ``lax.scan`` stepping.  The successor paper ("A Lightweight
Approach to Performance Portability with targetDP", 1609.01479) names
that glue as the remaining portability gap; task-graph layers (HPX,
2206.06302) close it with dependency graphs.  A :class:`Program` is that
graph, declaratively:

* a **Stage** binds one :class:`~repro.core.spec.KernelSpec` to named
  values — ``reads`` (one name per declared field, in order) and
  ``writes`` (one name per declared output) — plus its ``TARGET_CONST``
  bindings;
* a **Program** is an ordered tuple of stages over two kinds of names:
  **fields** (persistent, double-buffered step state — what
  ``step``/``run`` carry from one step to the next) and
  **intermediates** (step-local values, written before read, never
  materialised across steps).

Compiling a Program (:meth:`Program.compile`) lowers it through the
existing launch machinery (:func:`repro.core.api.launch` — plan cache,
executor registry, capability-aware prologue) into a single jitted step
function, adding exactly the glue applications used to hand-write:

a. **per-stage target routing** — each stage dispatches to the requested
   target, except pointwise stages under a stencil-only
   (``wants="halo_extended"``) executor, which route to ``"xla"``
   (generalising the ad-hoc fallback formerly buried in
   ``BinaryFluidSim``);
b. **one halo-exchange schedule per step** — ghost requirements are
   back-propagated through the stage graph (:meth:`Program.schedule`),
   so under ``shard_map`` every field is exchanged **once** per step, at
   the width the whole step needs; stages that read step-local
   intermediates through stencils *recompute* them on a ghost ring
   instead of triggering extra communication;
c. **buffer donation + ping-pong aliasing** —
   :meth:`CompiledProgram.run` executes ``nsteps`` under one
   ``lax.scan``; with ``donate=True`` the field buffers are donated so
   XLA aliases input and output state (no per-step reallocation);
d. **aggregated memory models** — :meth:`Program.plan` /
   :meth:`CompiledProgram.plan` build one
   :class:`~repro.core.api.LaunchPlan` per stage and aggregate the PR 3
   ``vmem_bytes_estimate`` / ``hbm_bytes_estimate`` models across the
   step;
e. **pencil/block decomposition with comm/compute overlap** — the mesh
   may shard up to ``ndim`` grid dimensions (mesh axis *k* ↔ grid dim
   *k*; one axis = slab, two = pencil, three = block).  Ghost exchanges
   run as **ordered per-dimension sweeps** (dim 0 first): the dim-1
   exchange transfers the already-dim-0-extended planes, so corner and
   edge ghosts arrive via the orthogonal neighbour without any explicit
   diagonal ``ppermute``.  With ``overlap=True``, each step's launches
   are split into an **interior** region that reads only local data —
   XLA's latency-hiding scheduler runs it while the ``ppermute``\\ s are
   in flight — plus two **boundary** slabs per sharded dim launched on
   the exchanged arrays (:func:`_overlap_regions`); the split is
   data-exact but region-shaped codegen may reassociate at ≤1 ULP, so
   it is opt-in.  :meth:`CompiledProgram.comm_stats` reports the
   analytic exchanged-bytes/ppermute budget per step.

:meth:`Program.execute` is the uncompiled single-step entry for callers
that manage their own ghost planes (``repro.kernels.ops.lb_fused_step``);
it runs the same stage pipeline eagerly, each launch hitting the shared
plan cache.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field as dc_field
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .api import launch as _launch
from .api import launch_plan as _launch_plan
from .api import _normalize_halo
from .lattice import Lattice
from .memory import BatchedConst
from .registry import executor_wants
from .spec import KernelSpec
from .state import ProgramState, validate_field
from .target import Target, as_target


# ---------------------------------------------------------------------------
# Stage — one KernelSpec bound to named values
# ---------------------------------------------------------------------------

def _as_names(x, what: str) -> tuple[str, ...]:
    if isinstance(x, str):
        x = (x,)
    names = tuple(str(n) for n in x)
    if not names:
        raise ValueError(f"a stage needs at least one {what} name")
    return names


def _freeze_consts(consts) -> tuple[tuple[str, Any], ...]:
    if not consts:
        return ()
    items = (sorted(consts.items()) if isinstance(consts, Mapping)
             else sorted(tuple(kv) for kv in consts))
    for k, _ in items:
        if not isinstance(k, str):
            raise TypeError(f"const names must be strings, got {k!r}")
    return tuple((k, v) for k, v in items)


@dataclass(frozen=True)
class Stage:
    """One launch of the step graph: a :class:`KernelSpec` bound to named
    program values.

    Args:
      spec: the kernel.  Its output counts must be declared (``out=``) —
        a Program wires outputs to names, so their arity/ncomp cannot be
        launch-inferred.
      reads: one name per declared field, in declaration order.
      writes: one name per declared output.  Writing a *field* name
        defines that field's next-step value; writing an *intermediate*
        name binds a step-local value for later stages.
      consts: ``TARGET_CONST`` bindings for this stage (mapping or item
        tuple; ``TargetConst`` values participate in the plan cache by
        content hash).
      name: display name (defaults to the spec's).
    """

    spec: KernelSpec
    reads: tuple[str, ...]
    writes: tuple[str, ...]
    consts: tuple[tuple[str, Any], ...] = dc_field(default=())
    name: str = ""

    def __post_init__(self):
        if not isinstance(self.spec, KernelSpec):
            raise TypeError(f"stage spec must be a KernelSpec, got "
                            f"{type(self.spec).__name__}")
        object.__setattr__(self, "reads", _as_names(self.reads, "read"))
        object.__setattr__(self, "writes", _as_names(self.writes, "write"))
        object.__setattr__(self, "consts", _freeze_consts(self.consts))
        if not self.name:
            object.__setattr__(self, "name", self.spec.name)
        if len(self.reads) != len(self.spec.fields):
            raise ValueError(
                f"stage {self.name!r} binds {len(self.reads)} read(s) but "
                f"kernel {self.spec.name!r} declares "
                f"{len(self.spec.fields)} field(s)")
        if self.spec.out is None:
            raise ValueError(
                f"stage {self.name!r}: kernel {self.spec.name!r} must "
                f"declare out= to participate in a Program (outputs are "
                f"wired to names)")
        if len(self.writes) != len(self.spec.out):
            raise ValueError(
                f"stage {self.name!r} binds {len(self.writes)} write(s) "
                f"but kernel {self.spec.name!r} declares "
                f"{len(self.spec.out)} output(s)")

    def consts_dict(self) -> dict:
        return dict(self.consts)


def stage(spec: KernelSpec, reads, writes, *, consts=None,
          name: str | None = None) -> Stage:
    """Ergonomic :class:`Stage` constructor (accepts bare-string names and
    dict consts)."""
    return Stage(spec, reads, writes, consts=_freeze_consts(consts),
                 name=name or "")


# ---------------------------------------------------------------------------
# Program — the ordered stage graph
# ---------------------------------------------------------------------------

def _grid_trim(arr: jax.Array, shape: tuple[int, ...],
               ext: tuple[int, ...], want: tuple[int, ...]) -> jax.Array:
    """Trim a ghost-extended grid ``(ncomp, *(shape + 2·ext))`` down to
    ``want`` ghost layers per dimension (``want <= ext`` everywhere)."""
    if ext == want:
        return arr
    for d, (e, w) in enumerate(zip(ext, want)):
        if e < w:
            raise ValueError(
                f"cannot widen ghost extent in dim {d}: have {e}, "
                f"need {w}")
        if e > w:
            arr = jax.lax.slice_in_dim(arr, e - w, e + w + shape[d],
                                       axis=d + 1)
    return arr


def _trim_fields(env: dict, fields: Sequence[str],
                 shape: tuple[int, ...]) -> dict:
    """Each field's grid in ``env`` trimmed to the interior ``shape``."""
    zeros = (0,) * len(shape)
    with jax.named_scope("tdp.trim"):
        return {f: _grid_trim(env[f][0], shape, env[f][1], zeros)
                for f in fields}


def resolve_stage_target(target: Target | str | None,
                         spec: KernelSpec,
                         stage_name: str | None = None) -> Target:
    """Per-stage target routing (the PR 3 capability surface, applied per
    stage): stencil stages keep the requested target; pointwise stages
    under a stencil-only (``wants="halo_extended"``) executor route to
    the ``"xla"`` executor at the same VVL.

    Per-stage tuning: ``Target.tuning`` keys of the reserved form
    ``"stage:<name>"`` hold a nested ``((knob, value), ...)`` assignment
    for that stage only (``tdp.autotune(..., per_stage=True)`` emits
    them).  All ``stage:*`` keys are stripped from the flat tuning, then
    the entry matching ``stage_name`` is merged over it — so a stage
    never sees another stage's knobs, and a per-stage value overrides
    the program-wide one."""
    tgt = as_target(target)
    if any(k.startswith("stage:") for k, _ in tgt.tuning):
        flat = {k: v for k, v in tgt.tuning
                if not k.startswith("stage:")}
        if stage_name is not None:
            mine = dict(tgt.tuning).get(f"stage:{stage_name}")
            if mine:
                flat.update(dict(mine))
        tgt = tgt.with_(tuning=flat)
    if spec.has_stencil:
        return tgt
    try:
        wants = executor_wants(tgt.executor)
    except ValueError:
        wants = "gathered"      # custom executor registered later
    if wants == "halo_extended":
        return tgt.with_(backend="xla", interpret=False)
    return tgt


class Program:
    """An ordered graph of :class:`Stage`\\ s over named fields and
    intermediates — one application *step* as a declarative object.

    Args:
      name: display name.
      stages: the launches, in execution order.
      fields: persistent state names (ordered — this is the order
        ``step``/``run`` tuples use).  A field's pre-step value is read
        until a stage writes it; the last write is the next-step value;
        unwritten fields pass through unchanged.
      intermediates: step-local names.  ``None`` infers them (every
        written name that is not a field); passing them explicitly
        validates the set exactly.
    """

    def __init__(self, name: str, stages: Sequence[Stage], *,
                 fields: Sequence[str],
                 intermediates: Sequence[str] | None = None):
        self.name = str(name)
        self.stages = tuple(stages)
        if not self.stages:
            raise ValueError(f"program {name!r} needs at least one stage")
        for st in self.stages:
            if not isinstance(st, Stage):
                raise TypeError(f"program {name!r}: stages must be Stage "
                                f"objects, got {type(st).__name__}")
        self.fields = _as_names(fields, "field")
        if len(set(self.fields)) != len(self.fields):
            raise ValueError(f"duplicate field names: {self.fields}")

        written = [w for st in self.stages for w in st.writes]
        inferred = tuple(dict.fromkeys(w for w in written
                                       if w not in self.fields))
        if intermediates is None:
            self.intermediates = inferred
        else:
            self.intermediates = tuple(str(n) for n in intermediates)
            if set(self.intermediates) != set(inferred):
                raise ValueError(
                    f"program {name!r}: declared intermediates "
                    f"{sorted(self.intermediates)} != written non-field "
                    f"names {sorted(inferred)}")
        overlap = set(self.fields) & set(self.intermediates)
        if overlap:
            raise ValueError(f"names {sorted(overlap)} are both fields "
                             f"and intermediates")

        # dataflow validation: reads resolve to fields or already-written
        # intermediates; every intermediate is consumed.
        known = set(self.fields) | set(self.intermediates)
        bound = set(self.fields)
        read_ever: set[str] = set()
        for st in self.stages:
            for r in st.reads:
                if r not in known:
                    raise ValueError(
                        f"stage {st.name!r} reads unknown name {r!r} "
                        f"(fields: {sorted(self.fields)}, intermediates: "
                        f"{sorted(self.intermediates)})")
                if r not in bound:
                    raise ValueError(
                        f"stage {st.name!r} reads intermediate {r!r} "
                        f"before any stage writes it")
                read_ever.add(r)
            bound.update(st.writes)
        dead = sorted(set(self.intermediates) - read_ever)
        if dead:
            raise ValueError(
                f"program {name!r}: intermediate(s) {dead} are written "
                f"but never read — drop them or make them fields")

        # per-name component counts (consistency across all bindings)
        self.ncomp: dict[str, int | None] = {n: None for n in known}

        def _record(n, c, where):
            if c is None:
                return
            c = int(c)
            if self.ncomp[n] is None:
                self.ncomp[n] = c
            elif self.ncomp[n] != c:
                raise ValueError(
                    f"name {n!r} has inconsistent ncomp: {self.ncomp[n]} "
                    f"vs {c} at {where}")

        for st in self.stages:
            for r, fs in zip(st.reads, st.spec.fields):
                _record(r, fs.ncomp, f"stage {st.name!r} read")
            for w, oc in zip(st.writes, st.spec.out):
                _record(w, oc, f"stage {st.name!r} write")

    def batched_consts(self) -> dict:
        """The program's per-member ensemble sweeps: ordered mapping of
        const name → :class:`~repro.core.memory.BatchedConst` over every
        stage binding one.  A name bound by several stages must bind the
        *same* sweep (content equality) — the fleet threads one value
        per name through the whole step."""
        out: dict[str, BatchedConst] = {}
        for st in self.stages:
            for k, v in st.consts:
                if not isinstance(v, BatchedConst):
                    continue
                prev = out.get(k)
                if prev is not None and prev != v:
                    raise ValueError(
                        f"program {self.name!r}: const {k!r} is bound to "
                        f"two different BatchedConst sweeps (stage "
                        f"{st.name!r} disagrees with an earlier stage); "
                        f"every stage must share one sweep per name")
                out[k] = v
        return out

    def __repr__(self):
        return (f"Program({self.name!r}, stages="
                f"{[st.name for st in self.stages]}, "
                f"fields={list(self.fields)}, "
                f"intermediates={list(self.intermediates)})")

    # -- the halo schedule -------------------------------------------------

    def schedule(self, ndim: int, open_dims: Sequence[bool]):
        """Back-propagate per-dimension ghost requirements through the
        stage graph — **the one halo-exchange schedule per step**.

        ``open_dims[d]`` marks dimensions whose ghosts are caller-managed
        (sharded slabs / pre-filled ghost planes); closed dimensions wrap
        periodically inside each launch and need nothing.

        Returns ``(field_widths, stage_geo)``:

        * ``field_widths[name]`` — ghost layers each *field* must carry at
          the start of the step (the exchange width: the max requirement
          over every stage that consumes its pre-step value);
        * ``stage_geo[i] = (ext_out, halo)`` — stage *i* computes its
          outputs on the interior extended by ``ext_out`` ghost layers
          (recompute-in-ghost for step-local intermediates read through
          stencils downstream) and launches with ``halo`` ghost width
          (the max stencil radius over its stencil-carrying reads, in
          open dimensions).
        """
        open_mask = tuple(bool(b) for b in open_dims)
        if len(open_mask) != ndim:
            raise ValueError(f"open_dims {open_mask} does not match "
                             f"ndim {ndim}")
        zeros = (0,) * ndim
        need: dict[str, tuple[int, ...]] = {f: zeros for f in self.fields}
        geo: list[tuple[tuple[int, ...], tuple[int, ...]]] = []
        for st in reversed(self.stages):
            outs = [need.pop(w, zeros) for w in st.writes]
            e_out = tuple(max(o[d] for o in outs) if open_mask[d] else 0
                          for d in range(ndim))
            radii = [s.radius_per_dim() for s in st.spec.stencils
                     if s is not None]
            h = tuple(max(r[d] for r in radii)
                      if radii and open_mask[d] else 0
                      for d in range(ndim))
            geo.append((e_out, h))
            for rname, s in zip(st.reads, st.spec.stencils):
                req = (e_out if s is None
                       else tuple(e + hh for e, hh in zip(e_out, h)))
                prev = need.get(rname, zeros)
                need[rname] = tuple(max(p, q) for p, q in zip(prev, req))
        geo.reverse()
        widths = {f: need.get(f, zeros) for f in self.fields}
        return widths, geo

    # -- stage execution core (shared by execute / compile) ----------------

    def _run_stages(self, stage_targets, shape: tuple[int, ...],
                    geo, env: dict, dyn: Mapping[str, Any] | None = None
                    ) -> dict:
        """Run all stages over ``env`` (name → ``(grid_array, ext)``),
        mutating and returning it.  ``geo`` is :meth:`schedule`'s
        per-stage ``(ext_out, halo)`` list.  ``dyn`` maps batched const
        names to this call's (possibly traced) per-member values —
        stages binding a :class:`BatchedConst` launch with the dynamic
        value instead of the baked sweep."""
        for st, tgt, (e_out, h) in zip(self.stages, stage_targets, geo):
            with jax.named_scope(f"tdp.stage.{st.name}"):
                self._run_stage(st, tgt, shape, e_out, h, env, dyn)
        return env

    @staticmethod
    def _run_stage(st: Stage, tgt: Target, shape, e_out, h, env: dict,
                   dyn) -> None:
        lat_shape = tuple(s + 2 * e for s, e in zip(shape, e_out))
        arrays = []
        with jax.named_scope("tdp.operands"):
            for rname, s in zip(st.reads, st.spec.stencils):
                arr, ext = env[rname]
                want = (e_out if s is None
                        else tuple(e + hh for e, hh in zip(e_out, h)))
                arr = _grid_trim(arr, shape, ext, want)
                arrays.append(arr.reshape(arr.shape[0], -1))
        consts = st.consts_dict()
        if dyn:
            for k, v in consts.items():
                if isinstance(v, BatchedConst) and k in dyn:
                    consts[k] = dyn[k]
        outs = _launch(st.spec, tgt, *arrays, lattice=Lattice(lat_shape),
                       halo=h if any(h) else None, consts=consts)
        outs = (outs,) if not isinstance(outs, tuple) else outs
        with jax.named_scope("tdp.outputs"):
            for w, o in zip(st.writes, outs):
                env[w] = (o.reshape(o.shape[0], *lat_shape), e_out)

    # -- eager execution with caller-managed ghosts ------------------------

    def execute(self, target: Target | str | None,
                state: Mapping[str, jax.Array], *,
                grid_shape: Sequence[int],
                halo: int | Sequence[int] | None = 0) -> dict:
        """Run one step eagerly over grid arrays, ghosts managed by the
        caller.

        ``state[name]`` is ``(ncomp, *(grid_shape + 2·halo))`` for every
        field; dimensions with ``halo[d] > 0`` carry caller-filled ghost
        planes (the sharded contract), dimensions with ``halo[d] == 0``
        wrap periodically.  Returns the next-step field grids over the
        interior.  Each launch dispatches through the shared plan cache,
        so repeated calls never re-trace.
        """
        shape = tuple(int(s) for s in grid_shape)
        ndim = len(shape)
        h0 = _normalize_halo(halo, ndim)
        open_mask = tuple(hh > 0 for hh in h0)
        widths, geo = self.schedule(ndim, open_mask)
        stage_targets = tuple(resolve_stage_target(target, st.spec, st.name)
                              for st in self.stages)
        env = {}
        for f in self.fields:
            if f not in state:
                raise ValueError(f"program {self.name!r}: state is "
                                 f"missing field {f!r}")
            short = [d for d in range(ndim) if h0[d] < widths[f][d]]
            if short:
                raise ValueError(
                    f"program {self.name!r}: field {f!r} needs "
                    f"{widths[f]} ghost layer(s) but the caller supplied "
                    f"halo={h0} (short in dim(s) {short})")
            env[f] = (state[f], h0)
        env = self._run_stages(stage_targets, shape, geo, env)
        return _trim_fields(env, self.fields, shape)

    # -- lowering ----------------------------------------------------------

    def compile(self, target: Target | str | None = None, *,
                grid_shape: Sequence[int], mesh=None,
                shard_axis: str | Sequence[str] | None = None,
                overlap: bool | None = None) -> "CompiledProgram":
        """Lower to one jitted step function (see
        :class:`CompiledProgram`).  ``mesh``/``shard_axis`` default to the
        target's hints; with a mesh, the step runs under ``shard_map``
        with mesh axis *k* sharding grid dim *k* (one name = slab, two =
        pencil, three = block) and one ghost-exchange round per field per
        sharded dim per step.  ``overlap=True`` opts into the
        comm/compute overlap schedule (interior launched while the
        exchanges are in flight); XLA codegen for the region shapes
        reassociates at the ≤1-ULP level, so it agrees with the default
        unsplit schedule under the cross-path contract
        (:func:`repro.core.state.check_fields`), as any other
        decomposition does, and stays opt-in (``None``/``False``)."""
        return CompiledProgram(self, target, grid_shape, mesh=mesh,
                               shard_axis=shard_axis, overlap=overlap)

    def autotune(self, target: Target | str | None,
                 example_state: Mapping[str, jax.Array], **kw):
        """Tune ``Target.tuning`` (and the executor) for this program —
        convenience front-end for :func:`repro.core.autotune.autotune`
        (which see for the keyword surface: ``space``, ``budget``,
        ``measure_steps``, ``timer``, ``cache_dir``, ...).  Returns a
        ``TuneResult`` ``(tuned_target, report)``."""
        from .autotune import autotune as _autotune
        return _autotune(self, target, example_state, **kw)

    def plan(self, target: Target | str | None = None, *,
             grid_shape: Sequence[int]) -> "ProgramPlan":
        """Aggregate the per-launch memory models across the step without
        compiling (single-device periodic geometry; for the sharded
        local geometry use :meth:`CompiledProgram.plan`)."""
        shape = tuple(int(s) for s in grid_shape)
        ndim = len(shape)
        _, geo = self.schedule(ndim, (False,) * ndim)
        stage_targets = tuple(resolve_stage_target(target, st.spec, st.name)
                              for st in self.stages)
        return _build_program_plan(self, stage_targets, shape, geo, {})


# ---------------------------------------------------------------------------
# the compiled step
# ---------------------------------------------------------------------------

def _shard_axes(shard_axis) -> tuple[str, ...]:
    """Normalise a ``shard_axis`` argument (name or sequence of names) to
    the ordered tuple of mesh axis names; axis *k* shards grid dim *k*."""
    if shard_axis is None:
        return ()
    if isinstance(shard_axis, str):
        return (shard_axis,)
    return tuple(str(a) for a in shard_axis)


def _exchange_hops(width: int, local_extent: int) -> list[tuple[int, int]]:
    """Hop plan for a ``width``-plane ghost exchange across shards of
    ``local_extent`` planes: ``[(hop, take), ...]`` — hop *j* transfers
    the ``take`` boundary planes of the rank ``±j`` neighbour.  One hop
    when the neighbour covers the width; one extra hop per additional
    shard when ``width > local_extent`` (maximal decompositions: a
    1-plane pencil feeding a radius-2 schedule reads from ranks ±2)."""
    hops = -(-width // local_extent)         # ceil: shards per side
    return [(j, min(local_extent, width - (j - 1) * local_extent))
            for j in range(1, hops + 1)]


def exchange_ghosts(arr: jax.Array, dim: int, width: int, nranks: int,
                    permute) -> jax.Array:
    """Extend a local shard ``(ncomp, *local)`` by ``width`` exchanged
    ghost planes on each side of grid dimension ``dim``.

    The transfer set is exactly the boundary planes (the paper's
    masked-copy idea), concatenated in global-coordinate order: the hop-j
    left ghosts sit left of the hop-(j-1) ones, mirroring on the right.
    ``permute(x, pairs)`` is the rank-permutation primitive —
    ``jax.lax.ppermute`` under ``shard_map`` (:func:`_exchange_dim`);
    tests inject a stacked-shard fake to cross-check the hop plan against
    a single-device roll reference.
    """
    ax = dim + 1                             # grid dim d is array axis d+1
    xl = arr.shape[ax]
    left, right = [], []
    for j, t in _exchange_hops(width, xl):
        fwd = [(i, (i + j) % nranks) for i in range(nranks)]  # recv from -j
        bwd = [(i, (i - j) % nranks) for i in range(nranks)]  # recv from +j
        last = jax.lax.slice_in_dim(arr, xl - t, xl, axis=ax)
        first = jax.lax.slice_in_dim(arr, 0, t, axis=ax)
        left.insert(0, permute(last, fwd))
        right.append(permute(first, bwd))
    return jnp.concatenate(left + [arr] + right, axis=ax)


def _exchange_dim(arr: jax.Array, axis_name: str, width: int,
                  dim: int) -> jax.Array:
    """:func:`exchange_ghosts` under ``shard_map``: mesh axis
    ``axis_name`` shards grid dim ``dim``."""
    n = jax.lax.axis_size(axis_name)
    return exchange_ghosts(
        arr, dim, width, n,
        lambda x, pairs: jax.lax.ppermute(x, axis_name, pairs))


def exchange_stats(widths: Mapping[str, Sequence[int]],
                   ncomp: Mapping[str, int | None],
                   local: Sequence[int], shard_dims: Sequence[int],
                   itemsize: int = 4) -> dict:
    """Analytic per-device cost of one step's exchange round.

    Mirrors the compiled sweep exactly: fields exchange dim by dim in
    ``shard_dims`` order, and a later dim's planes span the earlier
    dims' already-extended extents (that is how corner/edge ghosts
    travel), so its per-plane byte count grows accordingly.  Returns
    ``per_field`` rows plus the step totals ``exchanged_bytes_per_step``
    and ``ppermutes_per_step`` (the latter is checkable against
    ``collective-permute`` ops in the lowered HLO).
    """
    per_field = {}
    total_bytes = total_pp = 0
    for f, w in widths.items():
        c = int(ncomp.get(f) or 1)
        ext = list(int(s) for s in local)
        fbytes = fpp = 0
        sched = {}
        for d in shard_dims:
            wd = int(w[d])
            if not wd:
                continue
            plane = 1
            for dd, e in enumerate(ext):
                if dd != d:
                    plane *= e
            fbytes += 2 * wd * plane * c * itemsize
            fpp += 2 * len(_exchange_hops(wd, int(local[d])))
            sched[d] = wd
            ext[d] += 2 * wd
        per_field[f] = {"widths": sched, "bytes": fbytes,
                        "ppermutes": fpp}
        total_bytes += fbytes
        total_pp += fpp
    return {"per_field": per_field,
            "exchanged_bytes_per_step": total_bytes,
            "ppermutes_per_step": total_pp}


def _overlap_regions(local: Sequence[int], W: Sequence[int],
                     shard_dims: Sequence[int]):
    """The comm/compute-overlap partition of the local domain.

    ``W[d]`` is the step's max exchange width in dim ``d``.  Returns
    ``(interior, boundaries)`` where every region is ``(start, shape)``
    in local interior coordinates:

    * ``interior`` — the block at distance ≥ ``W[d]`` from every
      exchanged face: computable from local data alone, so it launches
      while the ``ppermute``\\ s are in flight;
    * ``boundaries`` — ``[(dim, lo_region, hi_region), ...]``, two
      ``W[d]``-thick slabs per exchanged dim, launched on the exchanged
      arrays.  The dim-*d* slabs span the *interior* extent in exchanged
      dims < *d* and the full local extent in dims > *d*, so the regions
      tile the local domain exactly once (corners belong to the lowest
      exchanged dim's slabs).
    """
    ndim = len(local)
    active = [d for d in shard_dims if W[d] > 0]
    i_start = tuple(W[d] if d in active else 0 for d in range(ndim))
    i_shape = tuple(local[d] - 2 * W[d] if d in active else local[d]
                    for d in range(ndim))
    bounds = []
    for d in active:
        start = tuple(W[dd] if (dd in active and dd < d) else 0
                      for dd in range(ndim))
        shape = tuple(W[d] if dd == d
                      else (local[dd] - 2 * W[dd]
                            if (dd in active and dd < d) else local[dd])
                      for dd in range(ndim))
        hi_start = tuple(local[d] - W[d] if dd == d else start[dd]
                         for dd in range(ndim))
        bounds.append((d, (start, shape), (hi_start, shape)))
    return (i_start, i_shape), bounds


def _run_region(program: Program, stage_targets, geo, widths, fields,
                sources: Mapping[str, tuple[jax.Array, tuple[int, ...]]],
                start: tuple[int, ...], shape: tuple[int, ...],
                dyn=None) -> dict:
    """Run the whole stage pipeline over one region of the local domain.

    ``sources[f] = (array, src_ext)`` covers interior coordinates
    ``[-src_ext[d], local[d] + src_ext[d])`` — raw local arrays
    (``src_ext = 0``, the interior region) or exchanged arrays
    (``src_ext = widths[f]``, boundary regions).  Each field is sliced to
    the region plus its own schedule width, so the region's launches see
    exactly the ghost geometry the full-domain pipeline would.
    """
    env = {}
    for f in fields:
        a, src_ext = sources[f]
        w = widths[f]
        for d in range(len(shape)):
            lo = start[d] - w[d] + src_ext[d]
            ln = shape[d] + 2 * w[d]
            if lo == 0 and ln == a.shape[d + 1]:
                continue
            a = jax.lax.slice_in_dim(a, lo, lo + ln, axis=d + 1)
        env[f] = (a, w)
    env = program._run_stages(stage_targets, shape, geo, env, dyn=dyn)
    return _trim_fields(env, fields, shape)


def _validate_decomposition(program: Program, grid_shape, open_mask):
    """Compile-time guard: every stencil-read dimension left *unsharded*
    wraps periodically inside each launch, which is only meaningful while
    the extent covers the stencil radius — a pencil misconfiguration
    (e.g. a radius-2 stencil on an unsharded extent-1 dim) must fail
    here, not deep inside ``lax.scan``."""
    for st in program.stages:
        for s in st.spec.stencils:
            if s is None:
                continue
            for d, r in enumerate(s.radius_per_dim()):
                if r and not open_mask[d] and r > grid_shape[d]:
                    sharded = [i for i, o in enumerate(open_mask) if o]
                    raise ValueError(
                        f"program {program.name!r} stage {st.name!r}: "
                        f"stencil {s.name!r} radius {r} in dim {d} "
                        f"exceeds the unsharded (periodic) extent "
                        f"{grid_shape[d]} — this decomposition (sharded "
                        f"dims {sharded}) leaves dim {d} too thin to "
                        f"wrap; shard dim {d} with a mesh axis or "
                        f"enlarge the grid")


class CompiledProgram:
    """A :class:`Program` lowered for one target + geometry.

    * :meth:`step` — one jitted step over the field dict;
    * :meth:`run` — ``nsteps`` under one jitted ``lax.scan``
      (``donate=True`` donates the field buffers: XLA aliases state in
      and out, the ping-pong);
    * :meth:`plan` — the aggregated :class:`ProgramPlan`;
    * :meth:`comm_stats` — the analytic exchange budget per step;
    * ``halo_schedule`` — field → dim-0 exchange width (sharded compiles
      only; the legacy slab view of ``exchange_schedule``);
    * ``exchange_schedule`` — field → ``{dim: width}`` over the sharded
      dims with a non-zero width (one exchange round each per step);
    * ``overlap`` — whether the compiled step uses the interior/boundary
      overlap split;
    * ``stage_targets`` — the per-stage routed targets (capability
      fallback applied).
    """

    def __init__(self, program: Program, target: Target | str | None,
                 grid_shape: Sequence[int], *, mesh=None,
                 shard_axis: str | Sequence[str] | None = None,
                 overlap: bool | None = None):
        self.program = program
        tgt = as_target(target)
        self.target = tgt
        self.grid_shape = tuple(int(s) for s in grid_shape)
        ndim = len(self.grid_shape)
        self.mesh = mesh if mesh is not None else tgt.mesh
        self.shard_axis = (shard_axis if shard_axis is not None
                           else (tgt.shard_axis or "data"))
        self.shard_axes = (_shard_axes(self.shard_axis)
                           if self.mesh is not None else ())
        self.stage_targets = tuple(resolve_stage_target(tgt, st.spec,
                                                        st.name)
                                   for st in program.stages)
        fields = program.fields
        zeros = (0,) * ndim
        # Per-member ensemble sweeps: their (traced) values enter the
        # core as trailing arguments after the field arrays, so one
        # compiled step serves every member under vmap (tdp.fleet).
        self.batched_consts = program.batched_consts()
        self.dyn_names = tuple(self.batched_consts)
        dyn_names = self.dyn_names
        nfields = len(fields)

        def _split(args):
            return args[:nfields], dict(zip(dyn_names, args[nfields:]))

        if self.mesh is None:
            self.local_shape = self.grid_shape
            open_mask = (False,) * ndim
            widths, geo = program.schedule(ndim, open_mask)
            _validate_decomposition(program, self.grid_shape, open_mask)
            self.halo_schedule: dict[str, int] = {}
            self.exchange_schedule: dict[str, dict[int, int]] = {}
            self._geo = geo
            self._widths = widths
            self._shard_dims: tuple[int, ...] = ()
            self._interior_shape = self.grid_shape
            self.overlap = False

            def core(*args):
                arrays, dyn = _split(args)
                env = {f: (a, zeros) for f, a in zip(fields, arrays)}
                env = program._run_stages(self.stage_targets,
                                          self.grid_shape, geo, env,
                                          dyn=dyn)
                return tuple(env[f][0] for f in fields)

        else:
            axes = self.shard_axes
            if not axes:
                raise ValueError(
                    f"program {program.name!r}: a mesh was given but "
                    f"shard_axis is empty — name the mesh axis(es) that "
                    f"shard grid dims 0..k")
            if len(axes) != len(set(axes)):
                raise ValueError(f"duplicate shard axes {axes}")
            if len(axes) > ndim:
                raise ValueError(
                    f"{len(axes)} shard axes {axes} for a {ndim}-D grid; "
                    f"mesh axis k shards grid dim k, so at most {ndim} "
                    f"axes apply")
            local = list(self.grid_shape)
            for d, ax in enumerate(axes):
                if ax not in self.mesh.shape:
                    raise ValueError(
                        f"shard axis {ax!r} is not a mesh axis "
                        f"(mesh has {tuple(self.mesh.shape)})")
                nsh = int(self.mesh.shape[ax])
                if self.grid_shape[d] % nsh != 0:
                    raise ValueError(
                        f"{'XYZ'[d] if d < 3 else f'dim-{d}'} extent "
                        f"{self.grid_shape[d]} not divisible by mesh "
                        f"axis {ax}={nsh}")
                local[d] = self.grid_shape[d] // nsh
            local = tuple(local)
            self.local_shape = local
            shard_dims = tuple(range(len(axes)))
            self._shard_dims = shard_dims
            open_mask = tuple(d < len(axes) for d in range(ndim))
            widths, geo = program.schedule(ndim, open_mask)
            self._geo = geo
            self._widths = widths
            self.halo_schedule = {f: widths[f][0] for f in fields}
            self.exchange_schedule = {
                f: {d: widths[f][d] for d in shard_dims if widths[f][d]}
                for f in fields}
            for d in shard_dims:
                w_max = max((widths[f][d] for f in fields), default=0)
                if w_max >= self.grid_shape[d]:
                    raise ValueError(
                        f"program {program.name!r} needs a {w_max}-plane "
                        f"ghost exchange in dim {d} but the global "
                        f"extent is only {self.grid_shape[d]} plane(s)")
            _validate_decomposition(program, self.grid_shape, open_mask)

            # Overlap is opt-in: splitting a launch into region-shaped
            # launches is *data*-exact, but XLA codegen for the region
            # shapes may reassociate float ops at the ≤1-ULP level, which
            # the cross-path contract (state.check_fields) allows, as it
            # does for any decomposition.  Feasibility: the interior must
            # be non-empty in every exchanged dim (thin pencils where the
            # exchange width swallows the whole shard stay unsplit).
            W = tuple(max((widths[f][d] for f in fields), default=0)
                      if open_mask[d] else 0 for d in range(ndim))
            (i_start, i_shape), bounds = _overlap_regions(local, W,
                                                          shard_dims)
            can_overlap = any(W) and all(s > 0 for s in i_shape)
            self.overlap = bool(overlap) and can_overlap
            self._interior_shape = i_shape if self.overlap else local

            @jax.named_scope("tdp.exchange")
            def _exchange_all(arrays):
                """Ordered per-dim sweep: dim 1 transfers the already-
                dim-0-extended planes, so corner ghosts arrive via the
                orthogonal neighbour (no diagonal ppermute)."""
                out = {}
                for f, a in zip(fields, arrays):
                    w = widths[f]
                    for d, ax in enumerate(axes):
                        if w[d]:
                            a = _exchange_dim(a, ax, w[d], d)
                    out[f] = a
                return out

            if not self.overlap:
                def core_local(*args):
                    arrays, dyn = _split(args)
                    ex = _exchange_all(arrays)
                    env = {f: (ex[f], widths[f]) for f in fields}
                    env = program._run_stages(self.stage_targets, local,
                                              geo, env, dyn=dyn)
                    out = _trim_fields(env, fields, local)
                    return tuple(out[f] for f in fields)
            else:
                def core_local(*args):
                    arrays, dyn = _split(args)
                    # Interior first, fed the *raw* local arrays — no
                    # data dependency on any ppermute, so XLA is free to
                    # run it while the exchanges are in flight.
                    raw = {f: (a, zeros) for f, a in zip(fields, arrays)}
                    out = _run_region(program, self.stage_targets, geo,
                                      widths, fields, raw, i_start,
                                      i_shape, dyn=dyn)
                    ex = _exchange_all(arrays)
                    exd = {f: (ex[f], widths[f]) for f in fields}
                    for d, lo, hi in reversed(bounds):
                        o_lo = _run_region(program, self.stage_targets,
                                           geo, widths, fields, exd,
                                           *lo, dyn=dyn)
                        o_hi = _run_region(program, self.stage_targets,
                                           geo, widths, fields, exd,
                                           *hi, dyn=dyn)
                        out = {f: jnp.concatenate(
                                   [o_lo[f], out[f], o_hi[f]], axis=d + 1)
                               for f in fields}
                    return tuple(out[f] for f in fields)

            pspec = PartitionSpec(*((None,) + axes
                                    + (None,) * (ndim - len(axes))))
            # The Pallas executors declare out_shape without a ``vma``
            # (how each output varies over the mesh axes), which
            # check_vma=True refuses: drop the check whenever any stage
            # dispatches off-xla.
            check = all(t.executor == "xla" for t in self.stage_targets)
            core = jax.shard_map(
                core_local, mesh=self.mesh,
                in_specs=(pspec,) * len(fields)
                + (PartitionSpec(),) * len(dyn_names),
                out_specs=(pspec,) * len(fields), check_vma=check)

        # Stable names for the trace: the jitted modules are
        # ``jit_tdp_<program>_step`` / ``_run``, the host spans
        # ``tdp.<program>.step`` / ``.run`` / ``.compile``.
        self._tag = re.sub(r"\W", "_", program.name)
        core.__name__ = core.__qualname__ = f"tdp_{self._tag}_step"
        self._core = core
        self._jit_step = jax.jit(core)
        self._run_cache: dict = {}
        self._dispatched: set = set()   # signatures seen, see `_span`

    # -- running -----------------------------------------------------------

    def _as_tuple(self, state: Mapping[str, jax.Array]):
        if isinstance(state, ProgramState) and state.ensemble is not None:
            raise ValueError(
                f"program {self.program.name!r}: state carries an "
                f"ensemble axis (ensemble={state.ensemble}) but this is "
                f"a single-member compile — run it through a fleet "
                f"(.vmap({state.ensemble})) or pass state.member(i)")
        arrays = []
        for f in self.program.fields:
            if f not in state:
                raise ValueError(
                    f"state for program {self.program.name!r} is missing "
                    f"field {f!r}; present: {sorted(state)}")
            a = state[f]
            validate_field(f, a, ncomp=self.program.ncomp.get(f),
                           grid_shape=self.grid_shape,
                           program=self.program.name)
            arrays.append(a)
        return tuple(arrays)

    def _wrap(self, state, outs) -> Mapping[str, jax.Array]:
        out = dict(zip(self.program.fields, outs))
        if isinstance(state, ProgramState):
            return ProgramState(out)
        return out

    def _require_unbatched(self, what: str):
        if self.dyn_names:
            raise ValueError(
                f"program {self.program.name!r} binds batched const(s) "
                f"{list(self.dyn_names)} (per-member ensemble sweeps); "
                f"{what} has no ensemble axis — compile a fleet with "
                f".vmap(batch) (tdp.fleet) instead")

    def _span(self, what: str, arrays, *key) -> jax.profiler.TraceAnnotation:
        """The host span around one dispatch of ``what`` (``step`` or
        ``run``): ``tdp.<program>.compile`` the first time jit sees this
        signature (each array's shape, dtype, weak type and sharding,
        plus ``key``), so it compiles or loads from the compile cache;
        ``tdp.<program>.<what>`` after that."""
        sig = (what, *key, *((a.shape, a.dtype, getattr(a, "weak_type", False),
                              getattr(a, "sharding", None)) for a in arrays))
        if sig not in self._dispatched:
            self._dispatched.add(sig)
            what = "compile"
        return jax.profiler.TraceAnnotation(f"tdp.{self.program.name}.{what}")

    def step(self, state: Mapping[str, jax.Array]):
        """One step: field mapping in (dict or
        :class:`~repro.core.state.ProgramState`), same kind out."""
        self._require_unbatched("CompiledProgram.step")
        arrays = self._as_tuple(state)
        with self._span("step", arrays):
            outs = self._jit_step(*arrays)
        return self._wrap(state, outs)

    def run(self, state: Mapping[str, jax.Array], nsteps: int, *,
            donate: bool = False, health=None):
        """``nsteps`` steps under one jitted ``lax.scan``.

        ``donate=True`` donates the input field buffers so XLA aliases
        them with the outputs (no per-step reallocation; the caller's
        arrays are consumed — feed each call the previous call's output,
        the ping-pong).  Compiled once per ``(nsteps, donate)``.
        Accepts a plain dict or a :class:`ProgramState`; returns the
        same kind.

        ``health``: an optional :class:`~repro.core.health.HealthPolicy`
        — the run splits into ``health.every``-step chunks (the same
        jitted scan iterated, so the trajectory is bit-identical to an
        unguarded run) with a host-side NaN/Inf/norm check between
        chunks; a violation raises
        :class:`~repro.core.health.HealthError` diagnosing the field
        and the ``every``-wide step range it appeared in.
        """
        self._require_unbatched("CompiledProgram.run")
        if health is not None:
            return self._run_guarded(state, int(nsteps), health,
                                     donate=donate)
        if nsteps <= 0:
            return self._wrap(state, tuple(state[f]
                                           for f in self.program.fields))
        key = (int(nsteps), bool(donate))
        fn = self._run_fn(*key)
        arrays = self._as_tuple(state)
        with self._span("run", arrays, *key):
            outs = fn(arrays)
        return self._wrap(state, outs)

    def _run_fn(self, nsteps: int, donate: bool):
        """The jitted scan of ``nsteps`` steps, compiled once per
        ``(nsteps, donate)``."""
        key = (nsteps, donate)
        fn = self._run_cache.get(key)
        if fn is None:
            core = self._core
            # A loop carries its state in one buffer, and a kernel that
            # cannot write over the state it reads (a Pallas launch)
            # would cost a copy of the state every trip: two steps a
            # trip ping-pong between that buffer and one more instead.
            # XLA's own ops update in place, so a program whose stages
            # all run on xla keeps one step a trip.
            unroll = 1 if all(t.executor == "xla"
                              for t in self.stage_targets) else 2

            def many(arrays):
                def body(carry, _):
                    return core(*carry), None
                out, _ = jax.lax.scan(body, arrays, None, length=nsteps,
                                      unroll=unroll)
                return out

            many.__name__ = many.__qualname__ = f"tdp_{self._tag}_run"
            fn = jax.jit(many, donate_argnums=(0,) if donate else ())
            self._run_cache[key] = fn
        return fn

    def _run_guarded(self, state, nsteps: int, health, *,
                     donate: bool = False):
        """Chunked run with health checks between chunks (see ``run``)."""
        from .health import check
        health.select_fields(self.program.fields)   # fail fast on typos
        done = 0
        while done < nsteps:
            chunk = min(health.every, nsteps - done)
            # donate only from the second chunk on: the first chunk's
            # inputs are the caller's arrays, which donate= promises to
            # consume only across the whole call, not per chunk — but an
            # intermediate chunk's inputs are ours to alias away.
            state = self.run(state, chunk, donate=donate and done > 0)
            check(health, state,
                  step_range=(done, done + chunk),
                  where=f"program {self.program.name!r}")
            done += chunk
        return state

    def vmap(self, batch: int) -> "repro.core.fleet.FleetProgram":  # noqa: F821
        """Lift this compiled step over a leading ensemble axis: a
        :class:`~repro.core.fleet.FleetProgram` stepping ``batch``
        independent trajectories (one per ensemble member) in one jitted
        launch — members never interact, so the fleet trajectory is
        bit-identical to ``batch`` single runs.  Sharded compiles
        compose the vmap *outside* ``shard_map``, so a decomposed fleet
        still runs one halo-exchange round per step."""
        from .fleet import FleetProgram
        return FleetProgram(self, batch)

    def plan(self) -> "ProgramPlan":
        """Aggregated memory models for this compile's local geometry."""
        return _build_program_plan(self.program, self.stage_targets,
                                   self.local_shape, self._geo,
                                   self.halo_schedule,
                                   self.exchange_schedule)

    def comm_stats(self, itemsize: int = 4) -> dict:
        """The analytic communication budget of one compiled step.

        Per-device, per-step: exchanged ghost bytes and ``ppermute``
        count (:func:`exchange_stats` — checkable against
        ``collective-permute`` ops in the lowered HLO), plus the
        decomposition shape and the overlap split's interior fraction
        (the share of local sites whose compute does not wait on any
        exchange).  ``itemsize`` defaults to float32 fields.
        """
        if self.mesh is None:
            return {"decomposition": "single", "shard_axes": (),
                    "mesh_axis_sizes": (), "local_shape": self.local_shape,
                    "exchange_schedule": {},
                    "exchanged_bytes_per_step": 0,
                    "ppermutes_per_step": 0, "per_field": {},
                    "overlap": False, "interior_fraction": 1.0}
        stats = exchange_stats(self._widths, self.program.ncomp,
                               self.local_shape, self._shard_dims,
                               itemsize)
        kinds = {1: "slab", 2: "pencil", 3: "block"}
        n_loc = 1
        for s in self.local_shape:
            n_loc *= s
        n_int = 1
        for s in self._interior_shape:
            n_int *= s
        stats.update(
            decomposition=kinds.get(len(self.shard_axes), "block"),
            shard_axes=self.shard_axes,
            mesh_axis_sizes=tuple(int(self.mesh.shape[a])
                                  for a in self.shard_axes),
            local_shape=self.local_shape,
            exchange_schedule=self.exchange_schedule,
            overlap=self.overlap,
            interior_fraction=(n_int / n_loc if self.overlap else 0.0))
        return stats

    def __repr__(self):
        return (f"CompiledProgram({self.program.name!r}, "
                f"target={self.target.executor!r}, "
                f"grid={self.grid_shape}, "
                f"sharded={self.mesh is not None})")


# ---------------------------------------------------------------------------
# aggregated memory models
# ---------------------------------------------------------------------------

class ProgramPlan:
    """Per-stage :class:`~repro.core.api.LaunchPlan`\\ s plus step-level
    aggregates.

    ``hbm_bytes_estimate`` **sums** the stage models — every executor
    operand and output materialised over one step (the per-step HBM
    footprint; stage transients are live at least until the next stage
    consumes them).  ``vmem_bytes_estimate`` takes the **max** — stages
    run sequentially, fast memory is reused.
    """

    __slots__ = ("name", "stages", "halo_schedule", "exchange_schedule")

    def __init__(self, name: str, stages, halo_schedule,
                 exchange_schedule=None):
        self.name = name
        self.stages = tuple(stages)          # (stage_name, LaunchPlan)
        self.halo_schedule = dict(halo_schedule)
        self.exchange_schedule = dict(exchange_schedule or {})

    def hbm_bytes_estimate(self, itemsize: int = 4) -> int:
        return sum(p.hbm_bytes_estimate(itemsize) for _, p in self.stages)

    def vmem_bytes_estimate(self, itemsize: int = 4) -> int:
        return max(p.vmem_bytes_estimate(itemsize) for _, p in self.stages)

    def per_stage(self, itemsize: int = 4) -> list[dict]:
        """One row per stage — the stage table (executor, capability,
        the dims each field wraps in-kernel, the y-block, memory
        models)."""
        return [{"stage": name, "executor": p.target.executor,
                 "wants": p.wants, "wrap_dims": p.wrap_dims,
                 "y_block": p.y_block,
                 "hbm_bytes_estimate": p.hbm_bytes_estimate(itemsize),
                 "vmem_bytes_estimate": p.vmem_bytes_estimate(itemsize)}
                for name, p in self.stages]

    def __repr__(self):
        return (f"ProgramPlan({self.name!r}, "
                f"stages={[n for n, _ in self.stages]}, "
                f"hbm={self.hbm_bytes_estimate()}, "
                f"vmem={self.vmem_bytes_estimate()})")


def _build_program_plan(program: Program, stage_targets,
                        shape: tuple[int, ...], geo, halo_schedule,
                        exchange_schedule=None) -> ProgramPlan:
    plans = []
    for st, tgt, (e_out, h) in zip(program.stages, stage_targets, geo):
        lat = Lattice(tuple(s + 2 * e for s, e in zip(shape, e_out)))
        lp = _launch_plan(st.spec, tgt, lattice=lat,
                          halo=h if any(h) else None,
                          consts=st.consts_dict())
        plans.append((st.name, lp))
    return ProgramPlan(program.name, plans, halo_schedule,
                       exchange_schedule)


# ---------------------------------------------------------------------------
# facade constructor
# ---------------------------------------------------------------------------

def program(name: str, stages: Sequence[Stage], *, fields: Sequence[str],
            intermediates: Sequence[str] | None = None) -> Program:
    """Build a :class:`Program` (``tdp.program(...)``)::

        prog = tdp.program(
            "lb_fused",
            [tdp.stage(FUSED_SPEC, reads=("f", "g"), writes=("f", "g"),
                       consts=collision_consts)],
            fields=("f", "g"))
        exe = prog.compile(tdp.Target("pallas_windowed"),
                           grid_shape=(64, 64, 64))
        state = exe.run(state, 100, donate=True)
    """
    return Program(name, stages, fields=fields, intermediates=intermediates)
