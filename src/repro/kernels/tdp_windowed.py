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
way, so a periodic launch is bit-identical to the same launch given
wrap-filled ghosts (``tests/test_windowed.py``).

Mechanically, the window is expressed through Pallas block indexing with
no overlap tricks: the prepared array is passed once per window slot
(operands alias one HBM buffer — XLA sees one value used W times), each
with a depth-1 BlockSpec ``lambda i: (0, i·plane_block + j, 0, ...)``
into the ghost-extended array, or ``(0, (i·plane_block + j − r₀ + X) mod
X, 0, ...)`` when x is periodic — so every grid step DMAs exactly its
window into VMEM, and a ``plane_block`` that does not divide X only
feeds output rows that are sliced away.

Memory model (vs the gathered path, per ``LaunchPlan`` estimates):

  HBM   Σ_i ncomp_i · prod(ext_d),  ext_d = shape_d (periodic) or
        shape_d + 2r_d (ghosts)          [was noffsets_i × interior]
  VMEM  3 × Σ_i ncomp_i · (plane_block + 2r₀) · prod(ext_rest)   per grid
        step, each plane padded to the (8, 128) tile
        (:meth:`~repro.core.api.LaunchPlan.window_blocks`)

— the ``noffsets×`` term is gone from both, and on a periodic lattice the
planes keep their unpadded (e.g. 128-lane) extent.  Compiled
(``interpret=False``) the kernel is given the device kind's VMEM limit
(:func:`repro.core.costmodel.vmem_limit_bytes`), and plans Mosaic cannot
lower — ``layout="aosoa"``, or a minor lattice extent that is not a
multiple of 128 lanes — are refused at plan build
(:class:`~repro.core.api.WindowShapeError`).

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
per vvl — trading a dense output store for broken bit-identity.  With
SoA output blocks every layout×vvl point is bit-identical to the SoA
path (pinned by ``tests/test_layout.py``).  ``vvl`` must divide the
*interior* plane site count exactly — validated at plan-build time by
:func:`repro.core.api.launch`; ghost-extended stencil operand planes
are zero-padded to a vvl multiple here and the pad lanes sliced away
in-kernel.
"""
from __future__ import annotations

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
            r = s.radius_per_dim()
            ext = tuple(sd if d in wrap else sd + 2 * rd
                        for d, (sd, rd) in enumerate(zip(shape, r)))
            if x.shape[1:] != ext:
                raise ValueError(
                    f"stencil field of kernel {plan.name!r} is not "
                    f"prepared to radius {r} with dims {wrap} wrapped "
                    f"in-kernel: got {tuple(x.shape[1:])}, want {ext}")
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
            # One depth-1 plane ref per window slot: operand j of this
            # field is blocked at x-plane i·p + j of the ghost-extended
            # array, or, with x periodic, at plane i·p + j − r₀ modulo X
            # of the interior one (kept non-negative: scalar rem
            # truncates).  All window operands alias one HBM value — the
            # only copies are the per-step HBM→VMEM window loads.
            for j in range(window):
                operands.append(x)
                if x_wraps:
                    def xplane(i, j=j, r0=r[0]):
                        return jax.lax.rem(i * p + (j - r0 + X), X)
                else:
                    def xplane(i, j=j):
                        return i * p + j
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

    scalar_consts, array_consts = _canonicalize_consts(plan.consts)
    const_names = list(array_consts)
    const_vals = [array_consts[k][1] for k in const_names]
    in_specs += [pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in const_vals]

    out_ncomp = tuple(plan.out_ncomp)
    # Outputs are written flat, (ncomp, p·V) lane-dense blocks: Mosaic
    # cannot reshape a one-component (1, V) value back into (1, p, Y, Z)
    # planes when Y is not a whole number of sublane tiles.
    out_specs = [pl.BlockSpec((c, chunk), lambda i: (0, i))
                 for c in out_ncomp]
    out_shape = [jax.ShapeDtypeStruct((c, (X + x_pad) * rest_n), dtype)
                 for c in out_ncomp]

    def body(*refs):
        it = iter(refs[:len(operands)])
        cref0 = len(operands)
        const_refs = refs[cref0:cref0 + len(const_names)]
        out_refs = refs[cref0 + len(const_names):]

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
        kw = dict(scalar_consts)
        for cname, cref in zip(const_names, const_refs):
            orig_shape, _ = array_consts[cname]
            kw[cname] = cref[...].reshape(orig_shape)
        if plan.interpret:
            # XLA's CPU backend contracts multiply-adds differently
            # depending on which data movement it fuses into the site
            # arithmetic; behind a barrier the arithmetic compiles the
            # same whether its chunk came from slices or rotations.
            chunks = jax.lax.optimization_barrier(chunks)
        vals = plan.kernel(*chunks, **kw)
        vals = (vals,) if not isinstance(vals, tuple) else vals
        for ref, v in zip(out_refs, vals):
            ref[...] = v.astype(ref.dtype)

    # Mosaic's default scoped-VMEM limit (16 MiB) is far below what a
    # 128² window needs; give the kernel the device's limit, the same
    # cap the plan-build guard held the window estimate to.
    compiler_params = None
    if not plan.interpret:
        compiler_params = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes(interpret=False))
    outs = pl.pallas_call(
        body,
        grid=(nwin,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=plan.interpret,
        compiler_params=compiler_params,
        name=f"tdp_windowed_{plan.name}_p{p}_{plan.layout}",
    )(*operands, *const_vals)

    n = X * rest_n
    return tuple(o[:, :n] for o in outs)
