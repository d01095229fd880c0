"""targetDP stencil executor: descriptors, backend parity, halo mode.

The contract under test (docs/stencil.md): a stencil site kernel written
once against ``(noffsets, ncomp, VVL)`` neighbour chunks produces the same
results, under the cross-path contract (``check_fields``), on the jnp
executor and the Pallas executor (interpret mode on the CPU), for
periodic (roll) gathers and for caller-supplied ghost planes (``halo=``),
including site counts that are not a VVL multiple.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Lattice,
    Stencil,
    STENCIL_D3Q19_PULL,
    STENCIL_GRAD_6PT,
    STENCIL_GRAD_19PT,
    check_fields,
    launch_stencil,
)
from repro.kernels.lb_collision import CV, NVEL
from repro.kernels.tdp_stencil import vmem_bytes_estimate
from repro.lb import stencil as lbst


class TestStencilDescriptor:
    def test_d3q19_matches_cv(self):
        np.testing.assert_array_equal(
            np.array([list(o) for o in STENCIL_D3Q19_PULL.offsets]),
            -CV.astype(int))
        assert STENCIL_D3Q19_PULL.radius == 1
        assert STENCIL_GRAD_6PT.noffsets == 7
        assert STENCIL_GRAD_19PT.noffsets == 19

    def test_index_lookup(self):
        assert STENCIL_GRAD_6PT.index((0, 0, 0)) == 0
        assert STENCIL_GRAD_6PT.index((1, 0, 0)) == 1
        with pytest.raises(KeyError):
            STENCIL_GRAD_6PT.index((2, 0, 0))

    def test_compose_radius_and_dedup(self):
        s = STENCIL_GRAD_6PT.compose(STENCIL_D3Q19_PULL)
        assert s.radius == 2
        assert s.noffsets == len(set(s.offsets))
        # every d - c_q offset is addressable
        for d in STENCIL_GRAD_6PT.offsets:
            for c in CV.astype(int):
                s.index(tuple(np.add(d, -c)))

    def test_validation(self):
        with pytest.raises(ValueError):
            Stencil("dup", ((0, 0), (0, 0)))
        with pytest.raises(ValueError):
            Stencil("empty", ())

    def test_vmem_estimate_counts_halo_rows(self):
        flat = vmem_bytes_estimate([19], [19], 128)
        halo = vmem_bytes_estimate([19], [19], 128, in_noffsets=[19])
        assert halo - flat == (19 * 19 - 19) * 128 * 4


class TestBackendParity:
    """xla vs pallas_interpret on the same single-source kernels —
    including a site count that is not a VVL multiple (padding path)."""

    @pytest.mark.parametrize("shape", [(4, 4, 4), (3, 4, 5)])
    def test_gradient_kernel(self, rng, shape):
        lat = Lattice(shape)
        phi = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
        outs = {}
        for backend in ("xla", "pallas_interpret"):
            g, l = launch_stencil(
                lbst.grad6_site_kernel, lat, [phi],
                stencil=STENCIL_GRAD_6PT, out_ncomp=(3, 1), vvl=64,
                backend=backend)
            outs[backend] = {"grad": g, "lap": l}
        check_fields(outs["xla"], outs["pallas_interpret"])

    @pytest.mark.parametrize("shape", [(4, 4, 4), (3, 4, 5)])
    def test_streaming_kernel(self, rng, shape):
        lat = Lattice(shape)
        f = jnp.asarray(rng.normal(size=(NVEL, lat.nsites)), jnp.float32)
        outs = []
        for backend in ("xla", "pallas_interpret"):
            outs.append(np.asarray(launch_stencil(
                lbst.stream_site_kernel, lat, [f],
                stencil=STENCIL_D3Q19_PULL, out_ncomp=NVEL, vvl=64,
                backend=backend)))
        check_fields({"f": outs[0]}, {"f": outs[1]})
        # cross-check against the grid-level roll semantics
        grid = np.asarray(f).reshape(NVEL, *shape)
        want = np.stack([np.roll(grid[q], shift=tuple(CV[q].astype(int)),
                                 axis=(0, 1, 2)) for q in range(NVEL)])
        np.testing.assert_array_equal(outs[0].reshape(NVEL, *shape), want)

    def test_fused_kernel_parity(self, rng):
        lat = Lattice((4, 4, 5))
        from repro.kernels import ops
        f = jnp.asarray(0.05 * rng.normal(size=(NVEL, lat.nsites)) + 1 / 19.,
                        jnp.float32)
        g = jnp.asarray(0.05 * rng.normal(size=(NVEL, lat.nsites)),
                        jnp.float32)
        a = ops.lb_fused_step(f, g, grid_shape=lat.shape, backend="xla",
                              vvl=64)
        b = ops.lb_fused_step(f, g, grid_shape=lat.shape,
                              backend="pallas_interpret", vvl=64)
        check_fields(dict(zip("fg", a)), dict(zip("fg", b)))


class TestHaloMode:
    """Ghost planes supplied by the caller (the sharded path's contract)
    reproduce the periodic gather when the ghosts hold the wrapped data."""

    @pytest.mark.parametrize("halo_x", [1, 2])
    def test_ghost_planes_match_periodic(self, rng, halo_x):
        shape = (4, 4, 4)
        lat = Lattice(shape)
        stc = (STENCIL_GRAD_6PT if halo_x == 1
               else STENCIL_GRAD_6PT.compose(STENCIL_GRAD_6PT))
        phi = np.asarray(rng.normal(size=(1, *shape)), np.float32)
        ext = np.concatenate(
            [phi[:, -halo_x:], phi, phi[:, :halo_x]], axis=1)

        def centre_sum(p_nb):
            acc = p_nb[0, 0]
            for i in range(1, stc.noffsets):
                acc = acc + p_nb[i, 0]
            return acc[None]

        a = launch_stencil(centre_sum, lat, [jnp.asarray(phi.reshape(1, -1))],
                           stencil=stc, out_ncomp=1, vvl=32)
        b = launch_stencil(centre_sum, lat, [jnp.asarray(ext.reshape(1, -1))],
                           stencil=stc, out_ncomp=1, vvl=32,
                           halo=(halo_x, 0, 0))
        check_fields([a], [b])

    def test_halo_too_small_rejected(self, rng):
        lat = Lattice((4, 4, 4))
        stc = STENCIL_GRAD_6PT.compose(STENCIL_GRAD_6PT)   # radius 2
        ext = jnp.zeros((1, 6 * 4 * 4), jnp.float32)       # halo 1 only
        with pytest.raises(ValueError, match="radius"):
            launch_stencil(lambda p: p[0], lat, [ext], stencil=stc,
                           out_ncomp=1, halo=(1, 0, 0))

    def test_wrong_extent_rejected(self):
        lat = Lattice((4, 4, 4))
        with pytest.raises(ValueError, match="extent"):
            launch_stencil(lambda p: p[0], lat,
                           [jnp.zeros((1, 60), jnp.float32)],
                           stencil=STENCIL_GRAD_6PT, out_ncomp=1)

    def test_mixed_pointwise_and_stencil_inputs(self, rng):
        """Pointwise inputs ride along at interior extent."""
        lat = Lattice((4, 4, 4))
        phi = jnp.asarray(rng.normal(size=(1, 64)), jnp.float32)
        scale = jnp.asarray(rng.normal(size=(1, 64)), jnp.float32)

        def k(p_nb, s):
            return s * (p_nb[1] - p_nb[2])

        for backend in ("xla", "pallas_interpret"):
            out = launch_stencil(k, lat, [phi, scale],
                                 stencil=(STENCIL_GRAD_6PT, None),
                                 out_ncomp=1, vvl=32, backend=backend)
            want = np.asarray(scale) * (
                np.roll(np.asarray(phi).reshape(1, 4, 4, 4), -1, axis=1)
                - np.roll(np.asarray(phi).reshape(1, 4, 4, 4), 1, axis=1)
            ).reshape(1, 64)
            check_fields([want], [out])
