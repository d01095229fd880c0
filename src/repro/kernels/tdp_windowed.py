"""Gather-free windowed Pallas stencil executor — ROADMAP stage (b).

The ``"pallas"`` executor receives stencil fields as pre-gathered
``(noffsets, ncomp, nsites)`` stacks: correct, but the gather
re-materialises every stencil field ``noffsets`` times in HBM (19× for
streaming, 57× for the fused LB g-neighbourhood) — the amplification the
paper's follow-up (arXiv:1609.01479) and Alpaka (arXiv:1602.08477) avoid
by serving stencil neighbourhoods from on-chip memory.

This executor declares ``wants="halo_extended"`` and
``wraps_periodic=True`` in the registry, so the launch prologue hands it
each stencil field **once**, as a grid ``(ncomp, *ext)``: a dimension
with caller ghosts (a sharded one) carries exactly the stencil radius
``r_d`` of them, and a periodic one arrives at its interior extent — on
one chip the field is the plain ``(ncomp, X, Y, Z)`` reshape, with no
copy in HBM.  Execution is an **x-plane grid**: step *i* computes
``plane_block`` output planes, and for each stencil field loads only the
``plane_block + 2·r₀`` x-planes its stencil can reach into VMEM.
Neighbour offsets are resolved *in-kernel* from the
:class:`~repro.core.lattice.Stencil` descriptor: the x component by
static window-slot selection, each y/z component by a static slice of
the ghost-extended plane (ghost dims) or a rotation of the loaded plane
by ``−off mod extent`` (periodic dims, ``pltpu.roll``).  Each rotated
plane is made once per distinct (window slot, dy, dz) and shared by the
offsets that read it; the compiler keeps only the components a site
kernel reads.  The ``(noffsets, ncomp, V)`` chunk every site kernel
already expects is assembled in fast memory and never exists in HBM.
Site kernels stay single-source; the values moved are the same either
way, so a periodic launch matches the same launch given wrap-filled
ghosts (``tests/test_windowed.py``, under the cross-path contract).

Mechanically, the window is expressed through Pallas block indexing with
no overlap tricks: the prepared array is passed once per window slot
(operands alias one HBM buffer — XLA sees one value used W times), each
with a depth-1 BlockSpec ``lambda i: (0, i·plane_block + j, 0, ...)``
into the ghost-extended array, or ``(0, (i·plane_block + j − r₀ + X) mod
X, 0, ...)`` when x is periodic — so every grid step DMAs exactly its
window into VMEM, and a ``plane_block`` that does not divide X only
feeds output rows that are sliced away.  Outputs are stored in the
lattice's grid layout, ``(ncomp, plane_block, *rest)`` blocks of a
``(ncomp, X, *rest)`` array, so the program's reshape of them to its grid
moves no data; a launch whose output rows are not a whole number of
sublane tiles (the pencil's ghost-extended φ, 130 rows) stores them flat
and is named ``..._p<plane_block>_flat_<layout>`` (:func:`_grid_store`).

Memory model (vs the gathered path, per ``LaunchPlan`` estimates):

  HBM   Σ_i ncomp_i · prod(ext_d),  ext_d = shape_d (periodic) or
        shape_d + 2r_d (ghosts)          [was noffsets_i × interior]
  VMEM  3 × Σ_i ncomp_i · (plane_block + 2r₀) · prod(ext_rest)   per grid
        step, each plane padded to the (8, 128) tile, ext_rest's y cut
        to y_block + 2h in a y-blocked plan
        (:meth:`~repro.core.api.LaunchPlan.window_blocks`)

— the ``noffsets×`` term is gone from both, and on a periodic lattice the
planes keep their unpadded (e.g. 128-lane) extent.  Compiled
(``interpret=False``) the kernel is given the device kind's VMEM limit
(:func:`repro.core.costmodel.vmem_limit_bytes`), and plans Mosaic cannot
lower — ``layout="aosoa"``, or a minor lattice extent that is not a
multiple of 128 lanes — are refused at plan build
(:class:`~repro.core.api.WindowShapeError`).

When that window does not fit the VMEM limit, the plan carries a
``y_block`` (:func:`repro.core.api._y_block_for`) and the launch runs a
second grid axis over y-blocks (:func:`_y_blocked_execute`): each window
slot then loads ``y_block`` rows plus ``h`` halo rows on either side, the
halo blocks' index wrapping modulo Y as x wraps in its slot index, and the
kernel is named ``..._p<plane_block>_yb<y_block>_<layout>``.  A plan
whose window fits keeps the one-axis grid below.

Tuning (``Target.tuning``): ``plane_block`` — output x-planes per grid
step (TLP chunk; window depth is ``plane_block + 2r₀``).  Default 1.

Layout axis (``Target.layout``): under ``"aosoa"`` every x-plane of
every *operand* is regrouped into vvl-site blocks
(:func:`repro.core.layout.plane_to_aosoa`), so each grid step's VMEM
window is a stack of **dense** ``(plane_block + 2r, nblk, ncomp, vvl)``
tiles instead of ``ncomp`` strided plane rows.  The in-kernel
un-interleave restores ``(ncomp, *ext_rest)`` planes before the offset
resolution, so site kernels are untouched.  Outputs are written as
plain SoA plane blocks in **both** layouts: re-interleaving the result
in-kernel feeds a transpose into the fused site-math cluster, and XLA
then contracts the arithmetic's mul+add chains into FMAs differently
per vvl.  The SoA output blocks were kept for bit-identity across
layouts, which the cross-path contract (``check_fields``) no longer
asks for; ``tests/test_layout.py`` holds every layout×vvl point to the
SoA path under it.  ``vvl`` must divide the
*interior* plane site count exactly — validated at plan-build time by
:func:`repro.core.api.launch`; ghost-extended stencil operand planes
are zero-padded to a vvl multiple here and the pad lanes sliced away
in-kernel.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costmodel import vmem_limit_bytes
from repro.core.layout import plane_to_aosoa

from .tdp_pointwise import _canonicalize_consts


def _prod(xs) -> int:
    out = 1
    for s in xs:
        out *= int(s)
    return out


def windowed_execute(plan, extended):
    """Registry executor entry (``wants="halo_extended"`` — see
    :mod:`repro.core.registry`).

    ``extended``: one array per field — ``(ncomp, *ext_shape)`` grids for
    stencil fields (ghost width = the stencil's per-dim radius in ghost
    dims, none in the dims of ``plan.wrap_dims``, which are wrapped
    here), ``(ncomp, nsites)`` for pointwise fields.
    """
    shape = plan.shape
    if shape is None:
        raise ValueError(
            f"windowed executor needs lattice geometry; kernel "
            f"{plan.name!r} was launched without a lattice")
    ndim = len(shape)
    stencils = plan.stencils
    p = int(plan.target.tune("plane_block", 1))
    if p <= 0:
        raise ValueError(f"plane_block must be positive, got {p}")
    if plan.y_block is not None:
        return _y_blocked_execute(plan, extended, p)
    X, rest = shape[0], tuple(shape[1:])
    rest_n = _prod(rest)
    nwin = -(-X // p)
    x_pad = nwin * p - X
    chunk = p * rest_n
    dtype = extended[0].dtype
    aosoa = plan.layout == "aosoa"
    vvl = int(plan.vvl)

    operands, in_specs, field_meta = [], [], []
    for x, s, wrap in zip(extended, stencils, plan.wrap_dims):
        ncomp = int(x.shape[0])
        if s is None:
            grid_x = x.reshape(ncomp, X, *rest)
            if x_pad:
                grid_x = jnp.pad(grid_x, [(0, 0), (0, x_pad)]
                                 + [(0, 0)] * (ndim - 1))
            if aosoa:
                # (X, nblk, ncomp, vvl): per-plane vvl-site tiles
                operands.append(plane_to_aosoa(grid_x, vvl))
                nblk = rest_n // vvl
                in_specs.append(pl.BlockSpec(
                    (p, nblk, ncomp, vvl), lambda i: (i, 0, 0, 0)))
            else:
                operands.append(grid_x)
                in_specs.append(pl.BlockSpec(
                    (ncomp, p, *rest),
                    lambda i: (0, i, *([0] * (ndim - 1)))))
            field_meta.append(("pointwise", ncomp, None, None, None, None))
        else:
            r, ext = _prepared_extent(plan, x, s, wrap)
            x_wraps = 0 in wrap
            if x_pad and not x_wraps:
                x = jnp.pad(x, [(0, 0), (0, x_pad)]
                            + [(0, 0)] * (ndim - 1))
            window = p + 2 * r[0]
            if aosoa:
                # flatten the extended rest dims and zero-pad each plane
                # to a vvl multiple (the interior-divisibility contract
                # doesn't extend to halo-widened planes); the in-kernel
                # unpack slices the pad lanes away
                xf = x.reshape(ncomp, int(x.shape[1]), -1)
                pad = (-int(xf.shape[-1])) % vvl
                if pad:
                    xf = jnp.pad(xf, [(0, 0), (0, 0), (0, pad)])
                x = plane_to_aosoa(xf, vvl)  # (Xext, nblk_e, ncomp, vvl)
                nblk_e = int(x.shape[1])
            # One depth-1 plane ref per window slot, all aliasing one HBM
            # value — the only copies are the per-step HBM→VMEM window
            # loads.
            for j in range(window):
                operands.append(x)
                xplane = _x_plane(j, p, r[0], X, x_wraps)
                if aosoa:
                    in_specs.append(pl.BlockSpec(
                        (1, nblk_e, ncomp, vvl),
                        lambda i, xplane=xplane: (xplane(i), 0, 0, 0)))
                else:
                    in_specs.append(pl.BlockSpec(
                        (ncomp, 1, *ext[1:]),
                        lambda i, xplane=xplane: (0, xplane(i),
                                                  *([0] * (ndim - 1)))))
            field_meta.append(("stencil", ncomp, s, r, ext, wrap))

    consts = _Consts(plan)
    in_specs += [pl.BlockSpec(c.shape, lambda i: (0, 0))
                 for c in consts.vals]

    out_ncomp = tuple(plan.out_ncomp)
    grid_out = _grid_store((p, *rest), dtype)
    block, full = (((p, *rest), (X + x_pad, *rest)) if grid_out
                   else ((chunk,), ((X + x_pad) * rest_n,)))
    out_specs = [pl.BlockSpec((c, *block),
                              lambda i: (0, i, *([0] * (len(block) - 1))))
                 for c in out_ncomp]
    out_shape = [jax.ShapeDtypeStruct((c, *full), dtype) for c in out_ncomp]

    def body(*refs):
        it = iter(refs[:len(operands)])
        cref0 = len(operands)
        const_refs = refs[cref0:cref0 + len(consts.names)]
        out_refs = refs[cref0 + len(consts.names):]

        def unpack_plane(blk, ncomp, rest_shape):
            # (nplanes, nblk, ncomp, vvl) AoSoA tile → SoA planes
            # (ncomp, nplanes, *rest_shape); extended planes may carry
            # trailing vvl-alignment pad lanes — sliced away here
            npl = int(blk.shape[0])
            y = jnp.transpose(blk, (2, 0, 1, 3))
            y = y.reshape(ncomp, npl, -1)
            rn = _prod(rest_shape)
            if int(y.shape[-1]) != rn:
                y = y[..., :rn]
            return y.reshape(ncomp, npl, *rest_shape)

        chunks = []
        for kind, ncomp, s, r, ext, wrap in field_meta:
            if kind == "pointwise":
                blk = next(it)[...]
                if aosoa:
                    blk = unpack_plane(blk, ncomp, rest)
                chunks.append(blk.reshape(ncomp, chunk))
                continue
            slots = [next(it) for _ in range(p + 2 * r[0])]
            if aosoa:
                slots = [unpack_plane(ref[...], ncomp, ext[1:])
                         for ref in slots]
            moved = {}

            def neighbour(slot, rest_off, slots=slots, moved=moved, r=r,
                          wrap=wrap):
                # window plane `slot`, (ncomp, *rest), moved by the y/z...
                # offsets rest_off: a caller-ghost dim is a static slice
                # of the extended plane, a periodic one a rotation by
                # −off mod extent.  Memoised on every prefix of rest_off,
                # so each rotation is made once and shared by the offsets
                # (and plane_block rows) that need it; the compiler keeps
                # only the components a site kernel reads.
                key = (slot, rest_off)
                if key not in moved:
                    if not rest_off:
                        v = slots[slot][:, 0]
                    else:
                        v = neighbour(slot, rest_off[:-1])
                        d, o = len(rest_off), rest_off[-1]
                        if d not in wrap:
                            v = jax.lax.slice_in_dim(
                                v, r[d] + o, r[d] + o + shape[d], axis=d)
                        elif o % shape[d]:
                            v = pltpu.roll(v, (-o) % shape[d], d)
                    moved[key] = v
                return moved[key]

            nb = []
            for off in s.offsets:
                rows = []
                for xl in range(p):
                    # plane (local x = xl) + offset: window slot is static
                    sl = neighbour(xl + r[0] + off[0], tuple(off[1:]))
                    rows.append(sl.reshape(ncomp, rest_n))
                nb.append(rows[0] if p == 1
                          else jnp.concatenate(rows, axis=-1))
            chunks.append(jnp.stack(nb))          # (noffsets, ncomp, V)

        if plan.with_site_index:
            base = pl.program_id(0) * chunk
            chunks.append(base + jax.lax.iota(jnp.int32, chunk))
        _site_kernel(plan, chunks, consts, const_refs, out_refs)

    outs = pl.pallas_call(
        body,
        grid=(nwin,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=plan.interpret,
        compiler_params=_compiler_params(plan),
        name=f"tdp_windowed_{plan.name}_p{p}"
             f"{'' if grid_out else '_flat'}_{plan.layout}",
    )(*operands, *consts.vals)

    if grid_out:
        return tuple(o[:, :X].reshape(o.shape[0], -1) for o in outs)
    return tuple(o[:, :X * rest_n] for o in outs)


def _grid_store(block, dtype) -> bool:
    """Whether a launch stores its outputs in the lattice's grid layout,
    as ``(ncomp, *block)`` blocks (``block`` = ``(plane_block, *rest)``)
    of a ``(ncomp, X, *rest)`` array, or flat, as ``(ncomp, p·V)`` blocks
    of ``(ncomp, sites)``.

    Mosaic reshapes the site kernel's ``(ncomp, V)`` value into a grid
    block only when the block's second-minor extent is a whole number of
    sublane tiles; its minor extent fills whole 128-lane vregs in every
    compiled launch (:class:`~repro.core.api.WindowShapeError`).  A grid
    output reaches the program's reshape to the grid as a bitcast.  A
    flat one is stored in tiles of 8 rows over the sites, which XLA
    copies into the grid's tiles and cuts to ``ncomp`` rows after every
    launch.  Decided from the shape alone, so interpret mode stores the
    outputs as the chip does.
    """
    sublanes = 32 // jnp.dtype(dtype).itemsize
    return len(block) < 2 or block[-2] % sublanes == 0


def _prepared_extent(plan, x, s, wrap):
    """The stencil radius of a field and the extent it must arrive at:
    the interior in the dims of ``wrap``, plus the radius elsewhere."""
    r = s.radius_per_dim()
    ext = tuple(sd if d in wrap else sd + 2 * rd
                for d, (sd, rd) in enumerate(zip(plan.shape, r)))
    if x.shape[1:] != ext:
        raise ValueError(
            f"stencil field of kernel {plan.name!r} is not "
            f"prepared to radius {r} with dims {wrap} wrapped "
            f"in-kernel: got {tuple(x.shape[1:])}, want {ext}")
    return r, ext


def _x_plane(j, p, r0, X, wraps):
    """The x-plane index of window slot ``j`` at grid step ``i``: plane
    ``i·p + j`` of the ghost-extended array, or, with x periodic, plane
    ``i·p + j − r₀`` modulo X of the interior one (kept non-negative:
    scalar rem truncates)."""
    if wraps:
        return lambda i: jax.lax.rem(i * p + (j - r0 + X), X)
    return lambda i: i * p + j


class _Consts:
    """A plan's TARGET_CONSTs: scalars closed over, arrays passed as
    full-block operands (``names``/``vals``) and reshaped back in-kernel."""

    def __init__(self, plan):
        self.scalars, self.arrays = _canonicalize_consts(plan.consts)
        self.names = list(self.arrays)
        self.vals = [self.arrays[k][1] for k in self.names]

    def kwargs(self, refs) -> dict:
        kw = dict(self.scalars)
        for cname, cref in zip(self.names, refs):
            kw[cname] = cref[...].reshape(self.arrays[cname][0])
        return kw


def _site_kernel(plan, chunks, consts, const_refs, out_refs):
    """Run the site kernel on the assembled chunks and store its outputs."""
    kw = consts.kwargs(const_refs)
    if plan.interpret:
        # XLA's CPU backend contracts multiply-adds differently
        # depending on which data movement it fuses into the site
        # arithmetic; behind barriers the arithmetic compiles the
        # same whether its chunk came from slices or rotations, and
        # whether its outputs are stored flat or as grid blocks.
        chunks = jax.lax.optimization_barrier(chunks)
    vals = plan.kernel(*chunks, **kw)
    vals = (vals,) if not isinstance(vals, tuple) else vals
    if plan.interpret:
        vals = jax.lax.optimization_barrier(vals)
    for ref, v in zip(out_refs, vals):
        v = v.astype(ref.dtype)
        if ref.shape[0] == 1 and ref.ndim > 2:
            # One component may be held a sublane per vreg, which Mosaic
            # cannot reshape into rows wider than one vreg (∇²φ at
            # Z = 256): store it row by row.
            n = ref.shape[-1]
            for k, row in enumerate(itertools.product(
                    *map(range, ref.shape[1:-1]))):
                ref[(0, *row)] = v[0, k * n:(k + 1) * n]
        else:
            ref[...] = v.reshape(ref.shape)


def _compiler_params(plan):
    # Mosaic's default scoped-VMEM limit (16 MiB) is far below what a
    # 128² window needs; give the kernel the device's limit, the same
    # cap the plan-build guard held the window estimate to.
    if plan.interpret:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=vmem_limit_bytes(interpret=False))


def _y_rows(x, ry, h, Y, Yp, wrap_y):
    """A stencil field's operand for y-blocked windows, and the number of
    ``h``-row blocks along its y dim.

    Block ``b`` reads its window as three blocks: the ``h`` rows before
    its own, its own ``yb`` rows, and the ``h`` rows after, the halo
    blocks indexed modulo the returned count.  A periodic y that the
    blocks tile exactly (``Yp == Y``) is read as it is, wrapping in that
    index.  Otherwise (caller ghosts in y, or a last block that runs past
    Y to ``Yp``) the rows are laid out cyclically in one copy: the
    interior, the ``ry`` rows that follow it (wrapped rows or the
    caller's ghosts), zeros up to row ``Yp + h``, then ``h`` rows that end
    in the ``ry`` rows preceding the interior.
    """
    if wrap_y and Yp == Y:
        return x, Y // h
    if wrap_y:
        lo, body, hi = x[:, :, Y - ry:], x, x[:, :, :ry]
    else:
        lo, body, hi = x[:, :, :ry], x[:, :, ry:ry + Y], x[:, :, ry + Y:]

    def zeros(n):
        return jnp.zeros((*x.shape[:2], n, *x.shape[3:]), x.dtype)
    rows = [body, hi, zeros(Yp + h - Y - ry), zeros(h - ry), lo]
    return jnp.concatenate(rows, axis=2), Yp // h + 2


def _y_blocked_execute(plan, extended, p):
    """The windowed launch over a ``(x windows, y-blocks)`` grid, for
    3-D SoA plans whose whole-plane window does not fit VMEM
    (``plan.y_block`` rows per block; :func:`repro.core.api._y_block_for`).

    Grid step ``(i, b)`` computes x-planes ``i·p … i·p + p − 1``, rows
    ``b·yb … b·yb + yb − 1``.  Per x slot a stencil field loads ``yb``
    rows plus ``h`` halo rows on either side (``h`` =
    ``LaunchPlan.y_halo_rows``), as three blocks whose halo block indices
    wrap modulo the y extent (:func:`_y_rows`), the way x wraps in its
    slot index; a y offset is a rotation of that window followed by an
    aligned slice of its ``yb`` middle rows.  z is resolved as in the
    whole-plane window: a rotation when periodic, a slice of the caller's
    ghosts otherwise.  Outputs are stored in the lattice's grid layout,
    as ``(ncomp, p, yb, Z)`` blocks at ``(i, b)`` of a ``(ncomp, X, Yp,
    Z)`` array (:func:`_grid_store`: ``yb`` is a multiple of the 8-row
    sublane tile), and rows past Y, if any, are cut after the call.
    """
    shape = plan.shape
    if len(shape) != 3 or plan.layout != "soa":
        raise ValueError(
            f"kernel {plan.name!r}: a y-blocked window needs a 3-D lattice "
            f"and layout='soa', got shape {shape}, layout {plan.layout!r}")
    X, Y, Z = (int(d) for d in shape)
    yb = int(plan.y_block)
    nwin, nyb = -(-X // p), -(-Y // yb)
    Yp = nyb * yb
    x_pad = nwin * p - X
    bs = yb * Z                       # sites of one x-plane in a block
    chunk = p * bs
    dtype = extended[0].dtype

    operands, in_specs, field_meta = [], [], []
    for x, s, wrap in zip(extended, plan.stencils, plan.wrap_dims):
        ncomp = int(x.shape[0])
        if s is None:
            grid_x = x.reshape(ncomp, X, Y, Z)
            if x_pad:
                grid_x = jnp.pad(grid_x, [(0, 0), (0, x_pad), (0, 0), (0, 0)])
            operands.append(grid_x)
            in_specs.append(pl.BlockSpec((ncomp, p, yb, Z),
                                         lambda i, b: (0, i, b, 0)))
            field_meta.append(("pointwise", ncomp, None, None, None, None))
            continue
        r, ext = _prepared_extent(plan, x, s, wrap)
        x_wraps = 0 in wrap
        if x_pad and not x_wraps:
            x = jnp.pad(x, [(0, 0), (0, x_pad), (0, 0), (0, 0)])
        h = plan.y_halo_rows(s)
        Ze = ext[2]
        if h:
            x, nh = _y_rows(x, r[1], h, Y, Yp, 1 in wrap)
            k = yb // h
        for j in range(p + 2 * r[0]):
            xplane = _x_plane(j, p, r[0], X, x_wraps)
            if h:
                operands.append(x)
                in_specs.append(pl.BlockSpec(
                    (ncomp, 1, h, Ze),
                    lambda i, b, xplane=xplane, k=k, nh=nh: (
                        0, xplane(i), jax.lax.rem(b * k - 1 + nh, nh), 0)))
            operands.append(x)
            in_specs.append(pl.BlockSpec(
                (ncomp, 1, yb, Ze),
                lambda i, b, xplane=xplane: (0, xplane(i), b, 0)))
            if h:
                operands.append(x)
                in_specs.append(pl.BlockSpec(
                    (ncomp, 1, h, Ze),
                    lambda i, b, xplane=xplane, k=k, nh=nh: (
                        0, xplane(i), jax.lax.rem((b + 1) * k, nh), 0)))
        field_meta.append(("stencil", ncomp, s, r, h, wrap))

    consts = _Consts(plan)
    in_specs += [pl.BlockSpec(c.shape, lambda i, b: (0, 0))
                 for c in consts.vals]
    out_specs = [pl.BlockSpec((c, p, yb, Z), lambda i, b: (0, i, b, 0))
                 for c in plan.out_ncomp]
    out_shape = [jax.ShapeDtypeStruct((c, nwin * p, Yp, Z), dtype)
                 for c in plan.out_ncomp]

    def body(*refs):
        it = iter(refs[:len(operands)])
        cref0 = len(operands)
        const_refs = refs[cref0:cref0 + len(consts.names)]
        out_refs = refs[cref0 + len(consts.names):]

        chunks = []
        for kind, ncomp, s, r, h, wrap in field_meta:
            if kind == "pointwise":
                chunks.append(next(it)[...].reshape(ncomp, chunk))
                continue
            windows = []
            for _ in range(p + 2 * r[0]):
                parts = [next(it) for _ in range(3 if h else 1)]
                windows.append(jnp.concatenate([ref[:, 0] for ref in parts],
                                               axis=1) if h else parts[0][:, 0])
            rows = yb + 2 * h
            moved = {}

            def neighbour(slot, rest_off, windows=windows, moved=moved,
                          r=r, h=h, wrap=wrap):
                # window `slot`, (ncomp, yb + 2h, Ze), moved by the y/z
                # offsets rest_off (memoised on every prefix): y by a
                # rotation of the window and a slice of its middle yb
                # rows, z as in the whole-plane window.
                key = (slot, rest_off)
                if key not in moved:
                    if not rest_off:
                        v = windows[slot]
                    else:
                        v = neighbour(slot, rest_off[:-1])
                        d, o = len(rest_off), rest_off[-1]
                        if d == 1:
                            if o:
                                v = pltpu.roll(v, (-o) % rows, 1)
                            v = jax.lax.slice_in_dim(v, h, h + yb, axis=1)
                        elif d not in wrap:
                            v = jax.lax.slice_in_dim(
                                v, r[d] + o, r[d] + o + Z, axis=d)
                        elif o % Z:
                            v = pltpu.roll(v, (-o) % Z, d)
                    moved[key] = v
                return moved[key]

            nb = []
            for off in s.offsets:
                blk = [neighbour(xl + r[0] + off[0], tuple(off[1:]))
                       .reshape(ncomp, bs) for xl in range(p)]
                nb.append(blk[0] if p == 1
                          else jnp.concatenate(blk, axis=-1))
            chunks.append(jnp.stack(nb))          # (noffsets, ncomp, V)

        if plan.with_site_index:
            k = jax.lax.iota(jnp.int32, chunk)
            base = pl.program_id(0) * (p * Y * Z) + pl.program_id(1) * bs
            if p > 1:
                k = (k // bs) * (Y * Z) + k % bs
            chunks.append(base + k)
        _site_kernel(plan, chunks, consts, const_refs, out_refs)

    outs = pl.pallas_call(
        body,
        grid=(nwin, nyb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=plan.interpret,
        compiler_params=_compiler_params(plan),
        name=f"tdp_windowed_{plan.name}_p{p}_yb{yb}_{plan.layout}",
    )(*operands, *consts.vals)

    return tuple(o[:, :X, :Y].reshape(o.shape[0], -1) for o in outs)
