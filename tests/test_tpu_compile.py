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
    lat = Lattice(grid)
    n_ext = int(np.prod([s + 2 * h for s, h in zip(grid, halo or (0,) * 3)]))
    args = [jax.ShapeDtypeStruct(
        (f.ncomp, n_ext if f.stencil is not None else lat.nsites),
        jnp.float32, sharding=sharding) for f in spec.fields]
    consts = {k: CONSTS[k] for k in spec.consts or ()}
    fn = jax.jit(lambda *a: tdp.launch(spec, target, *a, lattice=lat,
                                       halo=halo, consts=consts))
    return fn.lower(*args).compile()


def _has_pad(hlo: str) -> bool:
    return re.search(r"\bpad\(", hlo) is not None


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
