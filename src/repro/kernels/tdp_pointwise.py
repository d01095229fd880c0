"""Pallas executor for targetDP site kernels — the "CUDA implementation".

The paper's CUDA build of the macros assigns each thread a VVL-sized chunk of
sites (`TARGET_TLP`) and loops the innermost op over the chunk
(`TARGET_ILP`).  The TPU-native equivalent:

* the ``pallas_call`` **grid** plays the role of the CUDA thread grid: one
  grid step per VVL-chunk of sites;
* each input/output block is an explicit VMEM tile — ``(ncomp, VVL)`` for
  pointwise fields, ``(noffsets, ncomp, VVL)`` for stencil fields (the
  centre row plus one halo row per neighbour offset) — sites on the
  **lane** axis (SoA!), components on sublanes, so every jnp op inside the
  kernel body vectorises over lanes exactly as the strip-mined ILP loop
  vectorises over AVX lanes;
* ``VVL`` is the tunable block extent.  Multiples of 128 fill lane rows;
  larger values amortise HBM→VMEM latency (the paper's "m>1 can be faster"
  observation) at the cost of VMEM footprint:
  ``vmem_bytes ≈ sum_i(noffsets_i * ncomp_i * VVL * itemsize)`` which must
  stay ≲ 16 MiB (:func:`vmem_bytes_estimate`).

:func:`pallas_execute` is the registry executor behind
``Target("pallas")`` / ``Target("pallas", interpret=True)`` — registered
by :mod:`repro.core.api`, dispatched through
:func:`repro.core.registry.get_executor`.  ``interpret=True`` runs the
same kernel body on CPU for validation — this container has no TPU; tests
exercise the Pallas path through interpret mode and assert allclose
against the jnp executor (the "C implementation").
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def vmem_bytes_estimate(in_ncomp: Sequence[int], out_ncomp: Sequence[int],
                        vvl: int, in_noffsets: Sequence[int] | None = None,
                        itemsize: int = 4) -> int:
    """Static VMEM footprint of one grid step (inputs + outputs).

    ``in_noffsets[i]``: neighbour count of input i — 1 (default) for
    pointwise inputs, ``stencil.noffsets`` for stencil inputs (the halo
    rows each add a block row; see docs/stencil.md).  The stencil module
    (:mod:`repro.kernels.tdp_stencil`) re-exports this single rule.
    """
    if in_noffsets is None:
        in_noffsets = [1] * len(in_ncomp)
    in_rows = sum(int(o) * int(c) for o, c in zip(in_noffsets, in_ncomp))
    return (in_rows + sum(out_ncomp)) * vvl * itemsize


def _canonicalize_consts(consts: dict):
    """Split TARGET_CONST parameters into literal scalars (closed over — XLA
    inlines them) and array constants (side inputs: Pallas kernels may not
    capture traced values, so small read-only arrays ride along as full-block
    VMEM operands — the TPU analogue of ``__constant__`` memory)."""
    scalars, arrays = {}, {}
    for k, v in consts.items():
        if isinstance(v, (int, float, bool)):
            scalars[k] = v
        else:
            arr = jnp.asarray(v)
            orig_shape = arr.shape
            if arr.ndim == 0:
                arr2 = arr.reshape(1, 1)
            elif arr.ndim == 1:
                arr2 = arr.reshape(1, -1)
            else:
                arr2 = arr.reshape(arr.shape[0], -1)
            arrays[k] = (orig_shape, arr2)
    return scalars, arrays


def _run_pallas(kernel: Callable, vvl: int, with_site_index: bool,
                out_ncomp: tuple[int, ...], consts: dict, interpret: bool,
                gathered: Sequence[jax.Array], name: str,
                layout: str = "soa"):
    """Map ``kernel`` over VVL site chunks with explicit VMEM blocks.

    ``gathered``: per input, ``(noffsets, ncomp, n)`` for stencil fields or
    ``(ncomp, n)`` for pointwise ones — the output of the shared gather
    prologue in :mod:`repro.core.api`.  Grid = one step per VVL chunk.

    ``layout="aosoa"``: operands are reordered to the paper's AoSoA
    ``[site-block][component][lane]`` ordering
    (:func:`repro.core.layout.soa_to_aosoa`) and each grid step DMAs one
    *contiguous* block — for SoA the per-chunk BlockSpec strides across
    ``ncomp`` separate rows of HBM, for AoSoA it is a single dense tile.
    The kernel body still sees ``(ncomp, VVL)`` / ``(noffsets, ncomp,
    VVL)`` chunks with identical contents, so site kernels stay
    single-source and outputs agree across layouts (the cross-path
    contract).
    """
    from repro.core.api import pad_sites
    from repro.core.layout import aosoa_to_soa, soa_to_aosoa

    n = gathered[0].shape[-1]
    n_pad = -(-n // vvl) * vvl
    nchunks = n_pad // vvl
    dtype = gathered[0].dtype
    aosoa = layout == "aosoa"

    if aosoa:
        padded = tuple(soa_to_aosoa(x, vvl) for x in gathered)
    else:
        padded = tuple(pad_sites(x, vvl) for x in gathered)
    scalar_consts, array_consts = _canonicalize_consts(consts)
    const_names = list(array_consts)
    const_vals = [array_consts[k][1] for k in const_names]

    def body(*refs):
        in_refs = refs[:len(padded)]
        cref0 = len(padded)
        const_refs = refs[cref0:cref0 + len(const_names)]
        out_refs = refs[cref0 + len(const_names):]
        if aosoa:
            # (1, ..., ncomp, vvl) block → the site-kernel chunk shape
            chunks = [r[...].reshape(r.shape[1:]) for r in in_refs]
        else:
            chunks = [r[...] for r in in_refs]
        if with_site_index:
            # global site index of each lane in this chunk (TARGET_ILP offset
            # + baseIndex), computed from the grid position.
            base = pl.program_id(0) * vvl
            chunks.append(base + jax.lax.iota(jnp.int32, vvl))
        kw = dict(scalar_consts)
        for cname, cref in zip(const_names, const_refs):
            orig_shape, _ = array_consts[cname]
            kw[cname] = cref[...].reshape(orig_shape)
        vals = kernel(*chunks, **kw)
        vals = (vals,) if not isinstance(vals, tuple) else vals
        for r, v in zip(out_refs, vals):
            r[...] = v.reshape(r.shape).astype(r.dtype)

    def site_spec(x):
        if aosoa:
            return pl.BlockSpec((1, *x.shape[1:]),
                                lambda i: (i,) + (0,) * (x.ndim - 1))
        if x.ndim == 3:       # (noffsets, ncomp, vvl) halo block
            return pl.BlockSpec((x.shape[0], x.shape[1], vvl),
                                lambda i: (0, 0, i))
        return pl.BlockSpec((x.shape[0], vvl), lambda i: (0, i))

    in_specs = [site_spec(x) for x in padded] + [
        pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in const_vals
    ]
    if aosoa:
        out_specs = [pl.BlockSpec((1, c, vvl), lambda i: (i, 0, 0))
                     for c in out_ncomp]
        out_shape = [jax.ShapeDtypeStruct((nchunks, c, vvl), dtype)
                     for c in out_ncomp]
    else:
        out_specs = [pl.BlockSpec((c, vvl), lambda i: (0, i))
                     for c in out_ncomp]
        out_shape = [jax.ShapeDtypeStruct((c, n_pad), dtype)
                     for c in out_ncomp]

    outs = pl.pallas_call(
        body,
        grid=(nchunks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(*padded, *const_vals)

    if aosoa:
        return tuple(aosoa_to_soa(o, n) for o in outs)
    return tuple(o[:, :n] for o in outs)


def pallas_execute(plan, gathered: Sequence[jax.Array]):
    """Registry executor entry (see :mod:`repro.core.registry` for the
    ``executor(plan, gathered)`` contract)."""
    return _run_pallas(
        plan.kernel, plan.vvl, plan.with_site_index, tuple(plan.out_ncomp),
        plan.consts, plan.interpret, gathered,
        name=f"tdp_{plan.name}_vvl{plan.vvl}_{plan.layout}",
        layout=plan.layout)


def pallas_launch(kernel: Callable, vvl: int, with_site_index: bool,
                  out_ncomp: tuple[int, ...], consts: dict, interpret: bool,
                  inputs: tuple[jax.Array, ...]):
    """Pre-registry entry point, kept for direct callers."""
    outs = _run_pallas(
        kernel, vvl, with_site_index, tuple(out_ncomp), consts, interpret,
        inputs, name=f"tdp_{getattr(kernel, '__name__', 'site_kernel')}"
                     f"_vvl{vvl}")
    return outs[0] if len(outs) == 1 else outs
