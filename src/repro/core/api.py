"""The unified targetDP launch: ``tdp.launch(spec, target, *arrays)``.

One entry point replaces the old ``launch``/``launch_stencil`` fork: the
:class:`~repro.core.spec.KernelSpec` declares *what* (kernel body, field
roles, stencils, outputs), the :class:`~repro.core.target.Target`
declares *where/how* (executor, VVL, tuning), and this module owns the
single shared path every launch takes:

1. **validation** — field roles vs array ranks/extents, stencil geometry
   vs lattice + halo, const names;
2. **const unwrapping** — ``TargetConst`` → raw values, content-hashed
   into the cache key;
3. **plan caching** — compiled closures keyed on
   ``(spec, target, resolved VVL, lattice, halo, out, consts, registry
   version)``, so a mutated default VVL or a re-registered executor
   (even one re-registered with a different capability) can never hit a
   stale closure;
4. **the neighbour prologue** — *capability-aware*: executors declaring
   ``wants="gathered"`` (the default) get the periodic-roll /
   ghost-window gather into ``(noffsets, ncomp, nsites)`` stacks;
   executors declaring ``wants="halo_extended"`` get each stencil field
   **once**, as a halo-extended ``(ncomp, *ext_shape)`` grid
   (:func:`halo_extend`) — no ``noffsets×`` re-materialisation in HBM;
5. **dispatch** — through the executor registry
   (:mod:`repro.core.registry`).

Built-in executors registered here: ``"xla"`` (vmap over VVL chunks — the
paper's C build), ``"pallas"`` and ``"pallas_interpret"`` (explicit VMEM
tiling — the CUDA build), and ``"pallas_windowed"`` (gather-free x-plane
windowed VMEM loads — ROADMAP stencil-memory stage (b); Pallas modules
imported lazily so the core stays importable without Pallas).
"""
from __future__ import annotations

import functools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from .costmodel import vmem_limit_bytes
from .lattice import Lattice, Stencil
from .layout import aosoa_to_soa, soa_to_aosoa
from .memory import BatchedConst, TargetConst
from .registry import (
    get_executor_entry,
    register_executor,
    registry_version,
)
from .spec import FieldSpec, KernelSpec
from .target import Target, as_target


# ---------------------------------------------------------------------------
# shared helpers (padding, gathering, const handling)
# ---------------------------------------------------------------------------

def pad_sites(x: jax.Array, vvl: int) -> jax.Array:
    """Zero-pad the trailing site axis up to a VVL multiple (paper §III-C:
    the TLP loop strides in whole chunks).  Shared by every executor —
    padded lanes are sliced away after the launch, so kernels may produce
    garbage (even NaN) there."""
    n = x.shape[-1]
    n_pad = -(-n // vvl) * vvl
    if n_pad == n:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, n_pad - n)]
    return jnp.pad(x, widths)


def _prod_shape(shape) -> int:
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _tiled_bytes(shape, itemsize: int) -> int:
    """Bytes of a VMEM block of ``shape`` once Mosaic pads its minor dim
    to 128 lanes and its second-minor dim to whole ``8·4/itemsize``
    sublane rows."""
    dims = [int(s) for s in shape]
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        sub = 8 * 4 // itemsize
        dims[-2] = -(-dims[-2] // sub) * sub
    return _prod_shape(dims) * itemsize


@jax.named_scope("tdp.gather")
def gather_neighbors(x: jax.Array, shape: tuple[int, ...],
                     halo: tuple[int, ...], stencil: Stencil) -> jax.Array:
    """``(ncomp, nsites_ext)`` → ``(noffsets, ncomp, nsites)`` neighbour
    stack over the interior sites.

    Dimensions with ``halo[d] == 0`` wrap periodically (``roll``); those
    with ``halo[d] > 0`` read the caller-supplied ghost planes (offset
    window into the extended extent).
    """
    ext = tuple(s + 2 * h for s, h in zip(shape, halo))
    grid = x.reshape(x.shape[0], *ext)
    n = _prod_shape(shape)
    planes = []
    for off in stencil.offsets:
        g = grid
        for d, o in enumerate(off):
            ax = d + 1
            if halo[d]:
                g = jax.lax.slice_in_dim(g, halo[d] + o,
                                         halo[d] + o + shape[d], axis=ax)
            elif o:
                g = jnp.roll(g, -o, axis=ax)
        planes.append(g.reshape(x.shape[0], n))
    return jnp.stack(planes)


@jax.named_scope("tdp.halo_extend")
def halo_extend(x: jax.Array, shape: tuple[int, ...],
                halo: tuple[int, ...], stencil: Stencil) -> jax.Array:
    """``(ncomp, nsites_ext)`` → halo-extended grid ``(ncomp, *ext)`` with
    exactly ``stencil.radius_per_dim()`` ghost layers per dimension.

    The gather-free prologue for ``wants="halo_extended"`` executors
    (:mod:`repro.core.registry`): instead of rolling out one copy of the
    field per stencil offset, the field is padded **once** so every
    neighbour of every interior site is addressable by a static in-kernel
    shift.  Dimensions with ``halo[d] == 0`` wrap periodically
    (``jnp.pad(mode="wrap")``); dimensions with ``halo[d] > 0`` reuse the
    caller-supplied ghost planes, trimmed down to the stencil radius.
    """
    r = stencil.radius_per_dim()
    ext_in = tuple(s + 2 * h for s, h in zip(shape, halo))
    g = x.reshape(x.shape[0], *ext_in)
    widths = [(0, 0)]
    for d, (h, rd, s) in enumerate(zip(halo, r, shape)):
        if h:
            if h > rd:       # caller ghost wider than needed: trim
                g = jax.lax.slice_in_dim(g, h - rd, h + rd + s, axis=d + 1)
            widths.append((0, 0))
        else:
            if rd > s:
                raise ValueError(
                    f"stencil {stencil.name!r} radius {rd} in dim {d} "
                    f"exceeds the periodic extent {s}; refusing to "
                    f"wrap-pad more than one full period — supply "
                    f">= {rd} exchanged ghost planes in dim {d} "
                    f"(halo > 0) or enlarge the dimension")
            widths.append((rd, rd))
    if any(w != (0, 0) for w in widths):
        g = jnp.pad(g, widths, mode="wrap")
    return g


def _unwrap_consts(consts: Mapping[str, object]) -> dict:
    out = {}
    for k, v in consts.items():
        out[k] = v.value if isinstance(v, TargetConst) else v
    return out


def _consts_cache_key(consts: Mapping[str, object]):
    items = []
    for k in sorted(consts):
        v = consts[k]
        if isinstance(v, TargetConst):
            items.append((k, v))
        elif isinstance(v, (int, float, bool, str)):
            items.append((k, v))
        else:
            # Fall back to content hashing through TargetConst semantics.
            items.append((k, TargetConst(v)))
    return tuple(items)


def _split_consts(consts: Mapping[str, object]):
    """Partition launch consts into *static* values (hashable — closed
    over at jit time, in the plan cache key by content) and *dynamic*
    ones (jax arrays / tracers — per-call operands threaded into the
    jitted launch as trailing arguments; the cache key carries only
    their ``(name, shape, dtype)`` signature).  Dynamic consts are how
    per-member fleet parameters (``BatchedConst`` sweeps vmapped over an
    ensemble axis) flow through the shared plan cache without ever
    leaking a tracer into it."""
    static, dyn = {}, {}
    for k, v in consts.items():
        if isinstance(v, BatchedConst):
            raise ValueError(
                f"const {k!r} is a BatchedConst (per-member ensemble "
                f"sweep); a bare launch has no ensemble axis — bind it "
                f"through a Program stage and compile a fleet with "
                f"CompiledProgram.vmap(batch) (tdp.fleet)")
        if isinstance(v, jax.Array):
            dyn[k] = v
        else:
            static[k] = v
    return static, dyn


def _normalize_halo(halo, ndim) -> tuple[int, ...]:
    if halo is None:
        return (0,) * ndim
    if isinstance(halo, int):
        return (int(halo),) * ndim
    h = tuple(int(x) for x in halo)
    if len(h) != ndim:
        raise ValueError(f"halo {h} does not match lattice ndim {ndim}")
    return h


# ---------------------------------------------------------------------------
# launch plan — what an executor receives
# ---------------------------------------------------------------------------

class LaunchPlan:
    """Everything an executor needs to map one kernel over site chunks.

    Built (and cached) by :func:`launch`; executors are called as
    ``executor(plan, prepared)`` where ``prepared`` holds one array per
    field — shape depends on the executor's declared capability
    (``plan.wants``): ``"gathered"`` stencil fields are
    ``(noffsets, ncomp, n)`` neighbour stacks, ``"halo_extended"`` ones
    are ``(ncomp, *ext_shape)`` grids; pointwise fields are ``(ncomp, n)``
    either way.

    ``shape``/``halo``/``stencils`` carry the launch geometry (``None`` /
    all-``None`` for pure pointwise launches), so capability-declaring
    executors can resolve neighbour offsets themselves and so the
    :meth:`vmem_bytes_estimate` / :meth:`hbm_bytes_estimate` memory
    models are derivable from the plan alone (see docs/stencil.md).
    """

    __slots__ = ("kernel", "name", "vvl", "out_ncomp", "consts",
                 "with_site_index", "interpret", "target", "shape", "halo",
                 "stencils", "field_ncomp", "wants")

    def __init__(self, *, kernel, name, vvl, out_ncomp, consts,
                 with_site_index, interpret, target, shape=None, halo=None,
                 stencils=None, field_ncomp=None, wants="gathered"):
        self.kernel = kernel
        self.name = name
        self.vvl = vvl
        self.out_ncomp = out_ncomp
        self.consts = consts
        self.with_site_index = with_site_index
        self.interpret = interpret
        self.target = target
        self.shape = shape
        self.halo = halo
        self.stencils = tuple(stencils) if stencils is not None else None
        self.field_ncomp = (tuple(field_ncomp)
                            if field_ncomp is not None else None)
        self.wants = wants

    @property
    def layout(self) -> str:
        """Executor-internal memory layout (``Target.layout``): ``"soa"``
        or ``"aosoa"`` — the transforms live at field boundaries inside
        the executors (:mod:`repro.core.layout`), so the plan's operand
        and output *byte counts* are layout-invariant; only the AoSoA
        boundary transforms add traffic (see :meth:`hbm_bytes_estimate`).
        """
        return self.target.layout

    def with_consts(self, consts: Mapping[str, object]) -> "LaunchPlan":
        """Shallow copy with ``consts`` replaced — the per-call plan the
        dynamic-const path hands to the executor (same kernel, geometry
        and tuning; traced const values merged in)."""
        p = LaunchPlan.__new__(LaunchPlan)
        for s in LaunchPlan.__slots__:
            setattr(p, s, getattr(self, s))
        p.consts = dict(consts)
        return p

    # -- memory models ----------------------------------------------------
    #
    # Per-field rows: a gathered stencil field contributes noffsets·ncomp
    # rows (the HBM-materialised neighbour stack), a halo-extended one
    # ncomp rows over the (slightly larger) extended extent — the
    # ``noffsets×`` factor is exactly what ``wants="halo_extended"``
    # eliminates.  Fields with undeclared ncomp count as 1.

    def _fields(self):
        if self.field_ncomp is None:
            raise ValueError(
                f"plan {self.name!r} carries no field metadata; build it "
                f"through tdp.launch / tdp.launch_plan")
        stencils = self.stencils or (None,) * len(self.field_ncomp)
        return tuple(zip(self.field_ncomp, stencils))

    def _ext_shape(self, stencil):
        r = stencil.radius_per_dim()
        return tuple(s + 2 * rd for s, rd in zip(self.shape, r))

    def vmem_bytes_estimate(self, itemsize: int = 4) -> int:
        """Fast-memory footprint of one grid step (inputs + outputs).

        ``"gathered"`` executors hold ``noffsets_i · ncomp_i · VVL`` input
        rows per stencil field; ``"halo_extended"`` ones hold a
        ``(plane_block + 2·radius)``-plane window of the extended array —
        no ``noffsets`` factor (docs/stencil.md, "VMEM footprint rule").
        """
        if self.wants != "halo_extended":
            out_rows = sum(self.out_ncomp)
            in_rows = sum((s.noffsets if s is not None else 1) * c
                          for c, s in self._fields())
            return (in_rows + out_rows) * self.vvl * itemsize
        total = 3 * sum(b for _, b in self.window_blocks(itemsize))
        p = int(self.target.tune("plane_block", 1))
        if p > 1:
            # the p rows of every offset are concatenated into a
            # (noffsets, ncomp, p·V) chunk the compiler materialises
            v = p * _prod_shape(self.shape[1:])
            total += sum(_tiled_bytes((s.noffsets, c, v), itemsize)
                         for c, s in self._fields() if s is not None)
        return total

    def window_blocks(self, itemsize: int = 4) -> list[tuple[int, int]]:
        """The VMEM blocks of one windowed grid step, as ``(field index,
        bytes)`` pairs (index ``-1`` for outputs): every window plane of
        every stencil field, each pointwise block and each output block,
        with its two minor dims padded to Mosaic's ``(8·4/itemsize, 128)``
        tile.

        :meth:`vmem_bytes_estimate` counts each block three times: the
        pipeline's two buffers (the next step's DMA overlaps this step's
        compute) and the value the kernel body loads.  At
        ``plane_block=1`` the ``(noffsets, ncomp, V)`` chunk assembled
        from the loaded planes is never materialised whole — the site
        kernels read a few components per offset and the compiler keeps
        only the slices read — so it adds nothing; above 1 the rows are
        concatenated and the whole chunk is counted.  At 128² planes the
        estimate bounds what Mosaic allocates for the fused LB kernels:
        the compile-only tests in ``tests/test_tpu_compile.py`` compile
        them with the estimate as the limit.
        """
        if self.shape is None:
            raise ValueError("halo_extended estimates need a lattice shape")
        p = int(self.target.tune("plane_block", 1))
        rest = tuple(self.shape[1:])
        rest_n = _prod_shape(rest)
        aosoa = self.layout == "aosoa"
        vvl = int(self.vvl)
        blocks = []
        for i, (c, s) in enumerate(self._fields()):
            if s is None:
                shape = ((p, rest_n // vvl, c, vvl) if aosoa
                         else (c, p, *rest))
                blocks.append((i, _tiled_bytes(shape, itemsize)))
                continue
            ext_rest = self._ext_shape(s)[1:]
            shape = ((1, -(-_prod_shape(ext_rest) // vvl), c, vvl) if aosoa
                     else (c, 1, *ext_rest))
            window = p + 2 * s.radius_per_dim()[0]
            blocks.append((i, window * _tiled_bytes(shape, itemsize)))
        for c in self.out_ncomp:
            blocks.append((-1, _tiled_bytes((c, p * rest_n), itemsize)))
        return blocks

    def hbm_bytes_estimate(self, itemsize: int = 4) -> int:
        """Main-memory footprint of the executor's prepared operands plus
        outputs (excluding the caller's own input arrays).

        The gathered path materialises ``noffsets_i`` copies of every
        stencil field (the ~noffsets× amplification this framework's
        windowed executor exists to remove); the halo-extended path pays
        only the ghost-layer overhead ``prod(shape + 2·radius) /
        prod(shape)`` — independent of ``noffsets``.

        ``layout="aosoa"`` doubles the estimate: the SoA↔AoSoA boundary
        transforms re-materialise every prepared operand and output once
        (one extra HBM round-trip each) — the cost the autotuner's
        roofline model weighs the layout axis against.
        """
        if self.shape is None:
            raise ValueError("hbm_bytes_estimate needs a lattice shape")
        n = _prod_shape(self.shape)
        total = sum(self.out_ncomp) * n
        for c, s in self._fields():
            if s is None:
                total += c * n
            elif self.wants == "halo_extended":
                total += c * _prod_shape(self._ext_shape(s))
            else:
                total += c * s.noffsets * n
        if self.layout == "aosoa":
            total *= 2
        return total * itemsize

    def __repr__(self):
        return (f"LaunchPlan({self.name!r}, executor={self.target.executor!r}"
                f", vvl={self.vvl}, out={self.out_ncomp}, "
                f"wants={self.wants!r})")


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------

def _validate_arrays(spec: KernelSpec, arrays, lattice, halo):
    if len(arrays) != len(spec.fields):
        raise ValueError(
            f"kernel {spec.name!r} declares {len(spec.fields)} field(s) "
            f"but got {len(arrays)} array(s)")
    for i, (x, fs) in enumerate(zip(arrays, spec.fields)):
        if getattr(x, "ndim", None) != 2:
            raise ValueError(
                f"{fs.label(i)} of kernel {spec.name!r} has role "
                f"{fs.role!r} and must be an SoA array of shape "
                f"(ncomp, nsites); got rank "
                f"{getattr(x, 'ndim', '?')} array")
        if fs.ncomp is not None and int(x.shape[0]) != fs.ncomp:
            raise ValueError(
                f"{fs.label(i)} of kernel {spec.name!r} declares "
                f"ncomp={fs.ncomp} but the array has {x.shape[0]} "
                f"component(s)")

    if spec.has_stencil:
        if lattice is None:
            raise ValueError(
                f"kernel {spec.name!r} has stencil input(s) but the launch "
                f"is missing a lattice (neighbour geometry needs the shape)")
        h = _normalize_halo(halo, lattice.ndim)
        n_ext = _prod_shape(tuple(s + 2 * hh
                                  for s, hh in zip(lattice.shape, h)))
        for i, (x, fs) in enumerate(zip(arrays, spec.fields)):
            s = fs.stencil
            want = n_ext if s is not None else lattice.nsites
            if int(x.shape[-1]) != want:
                raise ValueError(
                    f"{fs.label(i)} extent {x.shape[-1]} != expected {want} "
                    f"({'extended' if s is not None else 'interior'}; "
                    f"shape={lattice.shape}, halo={h})")
            if s is None:
                continue
            if s.ndim != lattice.ndim:
                raise ValueError(
                    f"stencil {s.name!r} is {s.ndim}-D on a "
                    f"{lattice.ndim}-D lattice")
            for d, r in enumerate(s.radius_per_dim()):
                if h[d] and h[d] < r:
                    raise ValueError(
                        f"halo {h[d]} in dim {d} < stencil {s.name!r} "
                        f"radius {r}")
            if fs.halo == "periodic" and any(h):
                raise ValueError(
                    f"{fs.label(i)} declares halo policy 'periodic' but "
                    f"the launch supplies ghost planes (halo={h})")
            if fs.halo == "ghost" and not all(
                    h[d] >= r for d, r in enumerate(s.radius_per_dim())
                    if r):
                raise ValueError(
                    f"{fs.label(i)} declares halo policy 'ghost' but the "
                    f"launch halo {h} does not cover stencil "
                    f"{s.name!r} radius {s.radius_per_dim()}")
        return h

    # pure pointwise launch
    if halo is not None:
        hseq = (halo,) if isinstance(halo, int) else tuple(halo)
        if any(int(x) for x in hseq):
            raise ValueError("halo is only meaningful for stencil launches")
    nsite_set = {int(x.shape[-1]) for x in arrays}
    if len(nsite_set) != 1:
        raise ValueError(f"inputs disagree on site extent: "
                         f"{sorted(nsite_set)}")
    if lattice is not None:
        n = nsite_set.pop()
        if n not in (lattice.nsites, lattice.nsites_with_halo):
            raise ValueError(
                f"site extent {n} matches neither interior "
                f"({lattice.nsites}) nor halo-padded "
                f"({lattice.nsites_with_halo}) lattice")
    return None


def _validate_wrap_extents(spec: KernelSpec, lattice, halo):
    """Plan-build guard for :func:`halo_extend`'s periodic path: a
    ``wants="halo_extended"`` launch wrap-pads every dimension whose halo
    is 0 by the stencil radius, which this framework refuses when the
    radius exceeds the extent (e.g. a radius-2 stencil meeting a 1-plane
    pencil).  Raising here names the dim/radius/extent *before* tracing,
    instead of surfacing deep inside the jitted launch."""
    if lattice is None or not spec.has_stencil:
        return
    h = halo if halo is not None else (0,) * lattice.ndim
    for i, fs in enumerate(spec.fields):
        s = fs.stencil
        if s is None:
            continue
        for d, r in enumerate(s.radius_per_dim()):
            if r and h[d] == 0 and r > lattice.shape[d]:
                raise ValueError(
                    f"{fs.label(i)} of kernel {spec.name!r}: stencil "
                    f"{s.name!r} radius {r} in dim {d} exceeds the "
                    f"periodic extent {lattice.shape[d]} (halo_extend "
                    f"cannot wrap-pad a dimension thinner than the "
                    f"stencil radius); supply >= {r} ghost planes in "
                    f"dim {d} or enlarge it")


class WindowVmemError(ValueError):
    """A ``pallas_windowed`` launch whose VMEM window cannot fit.

    Raised at plan-build time (before any tracing) when
    :meth:`LaunchPlan.vmem_bytes_estimate` exceeds the fast-memory cap:
    the ``plane_block + 2·radius`` slab of some field is too large for
    one grid step.  The message names the worst field, its window bytes,
    and the cap.  ``tdp.autotune`` *prunes* candidates that raise this
    (the base target excepted — an unrunnable base is a caller error);
    shrinking ``plane_block`` or the y/z extents is the fix (y/z window
    blocking is a carried follow-up, see ROADMAP).
    """


def _check_window_vmem(plan: "LaunchPlan", spec: KernelSpec) -> None:
    """Refuse to build a windowed launch whose VMEM window exceeds the
    limit the compiler is given (:func:`costmodel.vmem_limit_bytes`)
    instead of letting Mosaic fail deep inside the jitted launch."""
    cap = vmem_limit_bytes(plan.interpret)
    total = plan.vmem_bytes_estimate()
    if total <= cap:
        return
    p = int(plan.target.tune("plane_block", 1))
    i, worst_bytes = max(plan.window_blocks(), key=lambda ib: ib[1])
    worst_label = "<output>" if i < 0 else spec.fields[i].label(i)
    raise WindowVmemError(
        f"kernel {plan.name!r} under executor "
        f"{plan.target.executor!r}: the plane_block={p} window needs an "
        f"estimated {total} bytes of VMEM (> cap {cap}); largest block "
        f"is {worst_label} at {worst_bytes} bytes per buffer — shrink "
        f"plane_block or the y/z extents")


class WindowShapeError(ValueError):
    """A compiled ``pallas_windowed`` launch whose block shapes Mosaic
    cannot lower for the TPU.

    The executor flattens each ``(ncomp, *rest)`` plane to ``(ncomp,
    prod(rest))`` in-kernel; Mosaic lowers that shape cast only when the
    minor lattice extent fills whole 128-lane vregs, and not at all for
    the AoSoA unpack.  Raised at plan-build time for ``interpret=False``
    targets, so Mosaic's ``unsupported shape cast`` never surfaces from
    inside a jitted step; interpret mode runs every shape.
    """


def _validate_compiled_window(spec: KernelSpec, target: Target,
                              lattice: Lattice | None) -> None:
    if (target.executor != "pallas_windowed" or target.interpret
            or lattice is None):
        return
    if target.layout == "aosoa":
        raise WindowShapeError(
            f"kernel {spec.name!r} under executor {target.executor!r}: "
            f"layout='aosoa' does not compile for the TPU (Mosaic cannot "
            f"lower the in-kernel AoSoA unpack); use layout='soa'")
    shape = lattice.shape
    if len(shape) >= 3 and shape[-1] % 128:
        raise WindowShapeError(
            f"kernel {spec.name!r} under executor {target.executor!r}: "
            f"minor lattice extent {shape[-1]} (shape {shape}) is not a "
            f"multiple of 128 lanes, so Mosaic cannot flatten the window "
            f"planes; pad the minor dimension or use the 'xla' executor")


def _validate_layout(spec: KernelSpec, target: Target,
                     lattice: Lattice | None, wants: str) -> None:
    """Plan-build validation of the AoSoA layout axis (satellite fix:
    an indivisible vvl used to surface deep inside the executor as a
    reshape error).  Gathered executors pad remainder sites, so any vvl
    is valid there; the *windowed* AoSoA path regroups each x-plane into
    vvl blocks and its *output* windows have no remainder story — vvl
    must divide the interior plane site count.  (Halo-extended stencil
    operand planes are zero-padded to a vvl multiple inside the
    executor, so only the interior extent constrains vvl.)"""
    if target.layout != "aosoa" or wants != "halo_extended":
        return
    vvl = target.resolve_vvl()
    if lattice is None:
        return
    shape = lattice.shape
    rest_n = _prod_shape(shape[1:]) if len(shape) > 1 else 1
    if rest_n % vvl:
        raise ValueError(
            f"kernel {spec.name!r} with layout='aosoa' under executor "
            f"{target.executor!r}: vvl={vvl} does not divide the "
            f"interior plane extent {rest_n} (= prod{tuple(shape[1:])}) "
            f"— the windowed AoSoA path regroups whole x-planes into "
            f"vvl-site blocks; pick a vvl dividing the plane site count")


# ---------------------------------------------------------------------------
# the launch itself
# ---------------------------------------------------------------------------

def _make_plan(spec: KernelSpec, target: Target, vvl: int,
               out_ncomp: tuple[int, ...], lattice: Lattice | None,
               halo: tuple[int, ...] | None, consts: dict,
               wants: str) -> LaunchPlan:
    return LaunchPlan(
        kernel=spec.fn, name=spec.name, vvl=vvl, out_ncomp=out_ncomp,
        consts=consts, with_site_index=spec.site_index,
        interpret=target.interpret, target=target,
        shape=lattice.shape if lattice is not None else None, halo=halo,
        stencils=spec.stencils,
        field_ncomp=tuple(fs.ncomp if fs.ncomp is not None else 1
                          for fs in spec.fields),
        wants=wants)


@functools.lru_cache(maxsize=4096)
def _build_plan(spec: KernelSpec, target: Target, vvl: int,
                out_ncomp: tuple[int, ...], lattice: Lattice | None,
                halo: tuple[int, ...] | None, const_key, dyn_sig,
                _registry_version):
    consts = _unwrap_consts(dict(const_key))
    dyn_names = tuple(k for k, _, _ in dyn_sig)
    entry = get_executor_entry(target.executor)
    executor = entry.fn
    plan = _make_plan(spec, target, vvl, out_ncomp, lattice, halo, consts,
                      entry.wants)
    if entry.wants == "halo_extended":
        _check_window_vmem(plan, spec)
    stencils = spec.stencils
    shape = lattice.shape if lattice is not None else None
    n_out = len(out_ncomp)
    nf = len(spec.fields)

    if entry.wants == "halo_extended":
        # Capability-aware prologue: pad each stencil field once instead
        # of rolling out one HBM copy per offset.
        def prepare(x, s):
            return x if s is None else halo_extend(x, shape, halo, s)
    else:
        def prepare(x, s):
            return x if s is None else gather_neighbors(x, shape, halo, s)

    def run(*args):
        # trailing args past the declared fields are dynamic const values
        arrays, dvals = args[:nf], args[nf:]
        p = plan
        if dyn_names:
            p = plan.with_consts({**plan.consts,
                                  **dict(zip(dyn_names, dvals))})
        prepared = tuple(prepare(x, s) for x, s in zip(arrays, stencils))
        outs = executor(p, prepared)
        outs = (outs,) if not isinstance(outs, (tuple, list)) else tuple(outs)
        if len(outs) != n_out:
            raise ValueError(
                f"executor {target.executor!r} returned {len(outs)} "
                f"output(s) for kernel {spec.name!r}; plan declares "
                f"{n_out}")
        return outs[0] if n_out == 1 else outs

    return jax.jit(run)


def launch(spec: KernelSpec, target: Target | str | None = None, /,
           *arrays, lattice: Lattice | None = None,
           halo: int | Sequence[int] | None = None,
           consts: Mapping[str, object] | None = None, **kw_consts):
    """Launch a declared kernel over the lattice (``TARGET_LAUNCH``).

    Args:
      spec: the :class:`KernelSpec` (build with ``@tdp.kernel`` or the
        constructor).
      target: a :class:`Target`, a backend-name string (coerced through
        :func:`~repro.core.target.as_target`), or ``None`` for the xla
        default.
      *arrays: one SoA target array per declared field — ``(ncomp,
        nsites)``; stencil fields span the halo-extended extent when
        ``halo`` is non-zero.
      lattice: grid descriptor.  Required when any field carries a
        stencil; optional (validation only) for pointwise launches.
      halo: per-dimension ghost width already present in stencil inputs
        (``0`` → periodic wrap).
      consts / **kw_consts: ``TARGET_CONST`` parameters (``TargetConst``
        or scalars), closed over at jit time.  ``lattice``, ``halo`` and
        ``consts`` are reserved keyword names — pass consts with those
        names through the ``consts=`` mapping.

    Returns one ``(ncomp_o, nsites)`` array per declared output (a bare
    array for single-output kernels).
    """
    if not isinstance(spec, KernelSpec):
        raise TypeError(
            f"tdp.launch expects a KernelSpec as first argument, got "
            f"{type(spec).__name__}; build one with @tdp.kernel / "
            f"tdp.KernelSpec (the legacy launch(kernel, lattice, inputs) "
            f"signature lives in repro.core.launch)")
    tgt = as_target(target)
    # fail fast on unknown executor names / capability mismatches
    entry = get_executor_entry(tgt.executor)
    if entry.wants == "halo_extended" and not spec.has_stencil:
        raise ValueError(
            f"executor {tgt.executor!r} declares wants='halo_extended' "
            f"(gather-free stencil windows) but kernel {spec.name!r} has "
            f"no stencil-carrying fields; use a 'gathered' executor such "
            f"as 'xla' or 'pallas' for pointwise kernels")
    arrays = tuple(arrays)
    if not arrays:
        raise ValueError("launch requires at least one input field")
    all_consts = dict(consts or {})
    all_consts.update(kw_consts)
    if spec.consts is not None:
        unknown = sorted(set(all_consts) - set(spec.consts))
        if unknown:
            raise ValueError(
                f"kernel {spec.name!r} does not declare const(s) "
                f"{unknown}; declared: {sorted(spec.consts)}")
    h = _validate_arrays(spec, arrays, lattice, halo)
    if entry.wants == "halo_extended":
        _validate_wrap_extents(spec, lattice, h)
    _validate_layout(spec, tgt, lattice, entry.wants)
    _validate_compiled_window(spec, tgt, lattice)
    vvl = tgt.resolve_vvl()
    out_ncomp = spec.out if spec.out is not None else (int(arrays[0].shape[0]),)
    static_consts, dyn_consts = _split_consts(all_consts)
    key = _consts_cache_key(static_consts)
    dyn_names = tuple(sorted(dyn_consts))
    dyn_sig = tuple((k, tuple(int(s) for s in dyn_consts[k].shape),
                     str(dyn_consts[k].dtype)) for k in dyn_names)
    fn = _build_plan(spec, tgt, vvl, out_ncomp, lattice, h, key, dyn_sig,
                     registry_version())
    return fn(*arrays, *(dyn_consts[k] for k in dyn_names))


def launch_plan(spec: KernelSpec, target: Target | str | None = None, *,
                lattice: Lattice | None = None,
                halo: int | Sequence[int] | None = None,
                consts: Mapping[str, object] | None = None) -> LaunchPlan:
    """Build (without compiling or launching) the :class:`LaunchPlan` a
    launch of ``spec`` under ``target`` would dispatch with — the
    introspection surface for the :meth:`LaunchPlan.vmem_bytes_estimate`
    and :meth:`LaunchPlan.hbm_bytes_estimate` memory models.

    Mirrors :func:`launch`'s resolution (executor capability, VVL,
    normalised halo) but takes no arrays; geometry checks that need them
    are skipped.
    """
    if not isinstance(spec, KernelSpec):
        raise TypeError(f"launch_plan expects a KernelSpec, got "
                        f"{type(spec).__name__}")
    tgt = as_target(target)
    entry = get_executor_entry(tgt.executor)
    if entry.wants == "halo_extended" and not spec.has_stencil:
        raise ValueError(
            f"executor {tgt.executor!r} declares wants='halo_extended' but "
            f"kernel {spec.name!r} has no stencil-carrying fields")
    if spec.has_stencil and lattice is None:
        raise ValueError(f"kernel {spec.name!r} has stencil input(s); "
                         f"launch_plan needs the lattice")
    h = (_normalize_halo(halo, lattice.ndim)
         if lattice is not None and spec.has_stencil else None)
    if entry.wants == "halo_extended":
        _validate_wrap_extents(spec, lattice, h)
    _validate_layout(spec, tgt, lattice, entry.wants)
    _validate_compiled_window(spec, tgt, lattice)
    if spec.out is not None:
        out_ncomp = spec.out
    elif spec.fields[0].ncomp is not None:
        # matches launch: out defaults to input 0's component count, and
        # validation pins the array to the declared ncomp
        out_ncomp = (spec.fields[0].ncomp,)
    else:
        raise ValueError(
            f"kernel {spec.name!r} declares neither out= nor an ncomp for "
            f"field 0 — its output count is only known at launch time, so "
            f"launch_plan cannot build a faithful plan")
    return _make_plan(spec, tgt, tgt.resolve_vvl(), tuple(out_ncomp),
                      lattice, h, _unwrap_consts(dict(consts or {})),
                      entry.wants)


# ---------------------------------------------------------------------------
# built-in executors
# ---------------------------------------------------------------------------

def xla_executor(plan: LaunchPlan, gathered):
    """The "C implementation": vmap the kernel body over VVL-sized chunks
    (TLP = the chunk loop, fused and threaded by XLA; ILP = jnp ops
    vectorised over the trailing VVL axis).  Handles pointwise chunks,
    stencil neighbour stacks, and the site-index role uniformly.

    ``plan.layout == "aosoa"``: operands are reordered through
    :func:`repro.core.layout.soa_to_aosoa` — site blocks outermost,
    ``(ncomp, vvl)`` tiles contiguous per block — and the chunk loop
    vmaps over the leading block axis.  Each chunk holds exactly the
    sites the SoA path's chunk *i* holds (same zero padding, same
    grouping), so results are bit-identical across layouts; only the
    physical operand ordering differs.
    """
    vvl = plan.vvl
    n = gathered[0].shape[-1]
    n_pad = -(-n // vvl) * vvl
    nchunks = n_pad // vvl
    aosoa = plan.layout == "aosoa"

    if aosoa:
        chunks = [soa_to_aosoa(x, vvl) for x in gathered]
        in_axes = [0] * len(chunks)
    else:
        chunks = [pad_sites(x, vvl).reshape(*x.shape[:-1], nchunks, vvl)
                  for x in gathered]
        in_axes = [x.ndim - 2 for x in chunks]
    body = (functools.partial(plan.kernel, **plan.consts)
            if plan.consts else plan.kernel)
    if plan.with_site_index:
        chunks.append(jnp.arange(n_pad, dtype=jnp.int32).reshape(nchunks,
                                                                 vvl))
        in_axes.append(0)
    n_out = len(plan.out_ncomp)
    out_ax = 0 if aosoa else 1
    outs = jax.vmap(body, in_axes=tuple(in_axes),
                    out_axes=out_ax if n_out == 1 else (out_ax,) * n_out
                    )(*chunks)
    outs = (outs,) if n_out == 1 else tuple(outs)
    if aosoa:
        return tuple(aosoa_to_soa(o, n) for o in outs)
    return tuple(o.reshape(o.shape[0], n_pad)[:, :n] for o in outs)


def _pallas_executor(plan: LaunchPlan, gathered):
    # Lazy import: the core stays importable without Pallas.
    from repro.kernels.tdp_pointwise import pallas_execute
    return pallas_execute(plan, gathered)


def _pallas_windowed_executor(plan: LaunchPlan, extended):
    from repro.kernels.tdp_windowed import windowed_execute
    return windowed_execute(plan, extended)


# ``tunables`` declares the Target.tuning keys consulted when
# dispatching under each name — the sweep/autotune contract.  The
# pointwise block knobs on "pallas" are consumed by the ops layer
# (repro.kernels.ops reads them off the same Target), not by
# pallas_execute itself; declaring them here keeps one authoritative
# table for `benchmarks/run.py --sweep` validation and `tdp.autotune`
# space construction.
_PALLAS_TUNABLES = ("block_f", "block_q", "block_k", "block_d", "block_t")

register_executor("xla", xla_executor)
register_executor("pallas", _pallas_executor, tunables=_PALLAS_TUNABLES)
register_executor("pallas_interpret", _pallas_executor,
                  tunables=_PALLAS_TUNABLES)
register_executor("pallas_windowed", _pallas_windowed_executor,
                  wants="halo_extended", tunables=("plane_block",))
