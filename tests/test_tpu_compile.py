"""Ahead-of-time compiles of the LB main path's Pallas kernels for a TPU
v5e, at the upstream per-device size (128³), and at a 256² cross-section
whose window VMEM holds only in y-blocks.

Nothing runs: each case lowers a launch for a described ``v5e:2x2``
topology and compiles it with the TPU compiler installed alongside JAX,
so a block shape Mosaic refuses, or a kernel that needs more VMEM than
it is given, fails here instead of on the chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers each import every test file.  Keep these cases
in this one file, so a single worker loads it.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tdp
from repro.core import costmodel as cm
from repro.core.lattice import Lattice
from repro.lb import programs as lbp
from repro.lb import stencil as lbst
from repro.lb.params import LBParams

GRID = (128, 128, 128)
KIND = "TPU v5 lite"
CONSTS = lbp.collision_consts(
    dtype=np.float32, **LBParams(A=0.125, B=0.125, kappa=0.02).as_kwargs())


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_v5e(monkeypatch):
    """Make the cost model answer for a v5e while this CPU process lowers
    for one: the windowed executor and the plan-build guard read the
    VMEM limit from the device kind's table row."""
    monkeypatch.setattr(cm, "device_kind", lambda: KIND)

    def set_limit(nbytes):
        monkeypatch.setitem(cm.DEVICE_PEAKS, KIND,
                            {**cm.DEVICE_PEAKS[KIND], "vmem_limit": nbytes})
    return set_limit


def _compile(spec, target, sharding, halo=None, grid=GRID):
    """Compile one launch as a program stage runs it
    (``Program._run_stage``): grid operands, ghost-extended by ``halo``
    for stencil fields, flattened for the launch, and its outputs
    reshaped back to the lattice's grid (``tdp.outputs``)."""
    lat = Lattice(grid)
    ext = tuple(s + 2 * h for s, h in zip(grid, halo or (0,) * 3))
    args = [jax.ShapeDtypeStruct(
        (f.ncomp, *(ext if f.stencil is not None else grid)),
        jnp.float32, sharding=sharding) for f in spec.fields]
    consts = {k: CONSTS[k] for k in spec.consts or ()}

    def stage(*a):
        outs = tdp.launch(spec, target, *(x.reshape(x.shape[0], -1)
                                          for x in a),
                          lattice=lat, halo=halo, consts=consts)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return tuple(o.reshape(o.shape[0], *grid) for o in outs)
    return jax.jit(stage).lower(*args).compile()


def _has_pad(hlo: str) -> bool:
    return re.search(r"\bpad\(", hlo) is not None


def _kernel_output_relayouts(hlo: str) -> list[str]:
    """The copies, slices, transposes and reshapes of an optimised HLO
    module whose operand is a windowed kernel's output, or a bitcast of
    one: the relayout XLA adds when the kernel stores its outputs in
    another layout than the grid's."""
    derived, found = set(), []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*?\s([a-z][\w\-]*)\((.*)",
                     line)
        if m is None:
            continue
        name, op, rest = m.groups()
        reads_kernel = bool(derived & set(re.findall(r"%([\w.\-]+)", rest)))
        if op == "custom-call" and name.startswith("tdp_windowed"):
            derived.add(name)
        elif op in ("get-tuple-element", "bitcast") and reads_kernel:
            derived.add(name)
        elif op in ("copy", "slice", "transpose", "reshape") and reads_kernel:
            found.append(name)
    return found


#: the windowed kernels of each fused mode (lb.programs.fused_program)
WINDOWED_KERNELS = {
    "one_launch": (lbst.FUSED_SPEC,),
    "two_launch": (lbst.PHI_STREAM_SPEC, lbst.FUSED_TWO_SPEC),
}


@pytest.mark.parametrize("mode", sorted(WINDOWED_KERNELS))
def test_windowed_fused_compiles_within_its_estimate(mode, one_chip,
                                                     on_v5e):
    """Each windowed kernel compiles when Mosaic's scoped-VMEM limit is
    the plan's own ``vmem_bytes_estimate()``: the estimate is at least
    what the compiler allocates, so the plan-build guard and autotune's
    pruning hold plans to a true bound.  On one chip every dimension is
    periodic and wrapped inside the kernel, so the launch holds no pad."""
    target = tdp.Target("pallas_windowed")
    limit = cm.DEVICE_PEAKS[KIND]["vmem_limit"]
    for spec in WINDOWED_KERNELS[mode]:
        est = tdp.launch_plan(spec, target, lattice=Lattice(GRID),
                              vmem_limit=limit).vmem_bytes_estimate()
        assert est <= limit
        on_v5e(est)
        hlo = _compile(spec, target, one_chip).as_text()
        assert "tpu_custom_call" in hlo
        assert not _has_pad(hlo)
        assert "_yb" not in hlo


def test_windowed_fused_y_blocked_compiles_within_its_estimate(one_chip,
                                                               on_v5e):
    """128×256×256: the whole-plane one_launch window (157 MB) exceeds
    the v5e limit, so the plan blocks y at the largest divisor of Y whose
    window fits (128 rows), and the blocked kernel compiles with its own
    estimate as Mosaic's limit, with no pad or copy of f and g outside
    the kernel."""
    grid = (128, 256, 256)
    target = tdp.Target("pallas_windowed")
    limit = cm.DEVICE_PEAKS[KIND]["vmem_limit"]
    plan = tdp.launch_plan(lbst.FUSED_SPEC, target, lattice=Lattice(grid))
    assert plan.y_block == 128
    whole = plan._replace(y_block=None).vmem_bytes_estimate()
    est = plan.vmem_bytes_estimate()
    assert est <= limit < whole
    on_v5e(est)
    hlo = _compile(lbst.FUSED_SPEC, target, one_chip, grid=grid).as_text()
    assert "tdp_windowed_fused_site_kernel_p1_yb128_soa" in hlo
    assert not _has_pad(hlo)


def test_windowed_pencil_compiles_within_its_estimate(one_chip, on_v5e):
    """The pencil's launch geometry: x and y carry one exchanged ghost
    plane, z is periodic and wrapped inside the kernel.  Both two_launch
    kernels compile within their estimate, with no pad in the launch."""
    target = tdp.Target("pallas_windowed")
    limit = cm.DEVICE_PEAKS[KIND]["vmem_limit"]
    halo = (1, 1, 0)
    for spec in WINDOWED_KERNELS["two_launch"]:
        plan = tdp.launch_plan(spec, target, lattice=Lattice(GRID),
                               halo=halo, vmem_limit=limit)
        assert plan.y_block is None
        assert all(w == (2,) for w, f in zip(plan.wrap_dims, spec.fields)
                   if f.stencil is not None)
        on_v5e(plan.vmem_bytes_estimate())
        hlo = _compile(spec, target, one_chip, halo=halo).as_text()
        assert "tpu_custom_call" in hlo
        assert not _has_pad(hlo)
        assert "_yb" not in hlo


#: each benchmark cell's windowed kernels as its program launches them:
#: (spec, lattice of the launch, caller ghosts, the kernel's name)
OUTPUT_LAYOUT_CASES = {
    "one_launch-128": (lbst.FUSED_SPEC, GRID, None,
                       "tdp_windowed_fused_site_kernel_p1_soa"),
    "y_blocked-128x256x256": (lbst.FUSED_SPEC, (128, 256, 256), None,
                              "tdp_windowed_fused_site_kernel_p1_yb128_soa"),
    "pencil-fused_two": (lbst.FUSED_TWO_SPEC, GRID, (1, 1, 0),
                         "tdp_windowed_fused_two_site_kernel_p1_soa"),
    # the collide prologue's gradients: one-component ∇²φ in 256-lane rows
    "gradients-128x256x256": (lbst.GRAD6_SPEC, (128, 256, 256), None,
                              "tdp_windowed_grad6_site_kernel_p1_soa"),
    # the pencil's streamed φ keeps a ghost ring: 130 rows, not a whole
    # number of 8-row sublane tiles, so its one component is stored flat
    "pencil-phi_stream-flat": (lbst.PHI_STREAM_SPEC, (130, 130, 128),
                               (1, 1, 0),
                               "tdp_windowed_streamed_phi_site_kernel"
                               "_p1_flat_soa"),
}


@pytest.mark.parametrize("case", sorted(OUTPUT_LAYOUT_CASES))
def test_windowed_outputs_need_no_relayout(case, one_chip, on_v5e):
    """Each cell's windowed kernels store their outputs in the lattice's
    grid layout, so the program's reshape of them to the grid compiles to
    no copy, slice, transpose or reshape of the kernel's output; only a
    launch whose output rows are not whole sublane tiles is stored flat,
    and named so."""
    spec, grid, halo, name = OUTPUT_LAYOUT_CASES[case]
    hlo = _compile(spec, tdp.Target("pallas_windowed"), one_chip,
                   halo=halo, grid=grid).as_text()
    assert f"%{name}." in hlo
    assert ("_flat" in name) == ("_flat_" in hlo)
    assert _kernel_output_relayouts(hlo) == []


def test_windowed_run_moves_no_state_outside_the_kernel(one_chip, on_v5e):
    """A call of the one-chip cell, 100 donated fused steps in one scan,
    compiles to no copy, slice or transpose as large as f or g: the
    kernel stores the grid's layout, and the scan takes two steps a trip,
    so the loop never copies the state it carries into the kernel."""
    exe = lbp.fused_program("one_launch", CONSTS).compile(
        tdp.Target("pallas_windowed"), grid_shape=GRID)
    state = jax.ShapeDtypeStruct((lbst.NVEL, *GRID), jnp.float32,
                                 sharding=one_chip)
    hlo = exe._run_fn(100, True).lower((state, state)).compile().as_text()
    assert "%tdp_windowed_fused_site_kernel_p1_soa." in hlo
    moves = [(op, shape) for shape, op in re.findall(
        r"= f32\[([\d,]+)\]\S* (copy|slice|transpose)\(", hlo)
        if np.prod([int(d) for d in shape.split(",")])
        >= lbst.NVEL * np.prod(GRID)]
    assert moves == []


def test_pallas_pointwise_collision_compiles(one_chip):
    compiled = _compile(lbst.COLLIDE_SPEC, tdp.Target("pallas"), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid, layout", [((64, 64, 64), "soa"),
                                          (GRID, "aosoa")])
def test_windowed_refuses_shapes_mosaic_cannot_lower(grid, layout):
    """A compiled windowed launch whose planes Mosaic cannot flatten (a
    minor extent that is not lane-aligned) or unpack (AoSoA) is refused
    with a named error when the plan is built; interpret mode runs it."""
    lat = Lattice(grid)
    with pytest.raises(tdp.WindowShapeError):
        tdp.launch_plan(lbst.FUSED_SPEC,
                        tdp.Target("pallas_windowed", layout=layout),
                        lattice=lat)
    tdp.launch_plan(lbst.FUSED_SPEC,
                    tdp.Target("pallas_windowed_interpret", layout=layout),
                    lattice=lat)
