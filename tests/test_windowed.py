"""The gather-free windowed stencil executor (``pallas_windowed``).

ROADMAP stencil-memory stage (b), pinned here (docs/stencil.md):

* ``pallas_windowed`` (interpret mode on this CPU container) is
  **bit-identical** to the ``xla`` executor on every LB stencil spec —
  STREAM, GRAD6, and both fused modes — including the 10-step fused
  trajectory at 16³ and caller-supplied ghost planes;
* the executor is registered through the *public*
  ``register_executor(..., wants="halo_extended")`` capability surface:
  a mock capability-declaring executor runs end-to-end with zero core
  edits, and feeding one a pointwise spec fails fast;
* the ``LaunchPlan`` memory models show the ``noffsets×`` HBM term gone:
  the windowed estimate depends only on the stencil *radius*, never on
  its offset count.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tdp
from repro.core import (
    Lattice,
    STENCIL_GRAD_6PT,
    STENCIL_GRAD_19PT,
    halo_extend,
    launch_plan,
)
from repro.kernels.lb_collision import NVEL
from repro.lb import stencil as lbst
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim

WINDOWED = tdp.Target("pallas_windowed", interpret=True)


def _rand_f(rng, n):
    return jnp.asarray(0.05 * rng.normal(size=(NVEL, n)) + 1 / 19.,
                       jnp.float32)


def _rand_g(rng, n):
    return jnp.asarray(0.05 * rng.normal(size=(NVEL, n)), jnp.float32)


class TestWindowedParity:
    """Bit-equivalence with the xla executor on the single-source LB
    specs — the portability contract extended to the gather-free path."""

    @pytest.mark.parametrize("shape", [(16, 16, 16), (5, 4, 3)])
    def test_stream_bit_identical(self, rng, shape):
        lat = Lattice(shape)
        f = _rand_f(rng, lat.nsites)
        a = tdp.launch(lbst.STREAM_SPEC, WINDOWED, f, lattice=lat)
        b = tdp.launch(lbst.STREAM_SPEC, tdp.Target("xla", vvl=64), f,
                       lattice=lat)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_grad6_bit_identical(self, rng):
        lat = Lattice((16, 16, 16))
        phi = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
        ga, la = tdp.launch(lbst.GRAD6_SPEC, WINDOWED, phi, lattice=lat)
        gb, lb = tdp.launch(lbst.GRAD6_SPEC, tdp.Target("xla", vvl=64), phi,
                            lattice=lat)
        np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

    @pytest.mark.parametrize("mode", ["one_launch", "two_launch"])
    def test_fused_step_bit_identical(self, rng, mode):
        from repro.kernels import ops
        lat = Lattice((16, 16, 16))
        f, g = _rand_f(rng, lat.nsites), _rand_g(rng, lat.nsites)
        a = ops.lb_fused_step(f, g, grid_shape=lat.shape, mode=mode,
                              target=WINDOWED)
        b = ops.lb_fused_step(f, g, grid_shape=lat.shape, mode=mode,
                              backend="xla", vvl=64)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    @pytest.mark.parametrize("plane_block", [2, 3])
    def test_plane_block_tuning_bit_identical(self, rng, plane_block):
        """plane_block > 1 (and X not a multiple of it) only changes the
        TLP chunking, never the numbers."""
        lat = Lattice((7, 4, 5))
        f = _rand_f(rng, lat.nsites)
        t = WINDOWED.with_(tuning={"plane_block": plane_block})
        a = tdp.launch(lbst.STREAM_SPEC, t, f, lattice=lat)
        b = tdp.launch(lbst.STREAM_SPEC, "xla", f, lattice=lat)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_ghost_halo_mode_bit_identical(self, rng):
        """Caller-filled ghost planes (the sharded contract, width 2 for
        the radius-2 fused neighbourhood) reproduce the periodic gather."""
        from repro.kernels import ops
        shape = (8, 8, 8)
        n = 512
        f, g = _rand_f(rng, n), _rand_g(rng, n)
        fg = np.asarray(f).reshape(NVEL, *shape)
        gg = np.asarray(g).reshape(NVEL, *shape)

        def ext2(x):
            return np.concatenate([x[:, -2:], x, x[:, :2]], axis=1)

        fe = jnp.asarray(ext2(fg).reshape(NVEL, -1))
        ge = jnp.asarray(ext2(gg).reshape(NVEL, -1))
        a = ops.lb_fused_step(fe, ge, grid_shape=shape, halo=(2, 0, 0),
                              mode="one_launch", target=WINDOWED)
        b = ops.lb_fused_step(f, g, grid_shape=shape, mode="one_launch",
                              backend="xla", vvl=64)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_fused_trajectory_bit_identical_to_xla(self):
        """The acceptance pin: 10 fused steps at 16³ on pallas_windowed
        produce the bit-identical trajectory to the same steps on xla."""
        p = LBParams(A=0.125, B=0.125, kappa=0.02)
        a = BinaryFluidSim((16, 16, 16), params=p, fused="one_launch")
        b = BinaryFluidSim((16, 16, 16), params=p, fused="one_launch",
                           target=WINDOWED)
        st0 = a.init_spinodal(seed=3, noise=0.05)
        ua = a.step(st0, 10)
        ub = b.step(st0, 10)
        np.testing.assert_array_equal(np.asarray(ua.f), np.asarray(ub.f))
        np.testing.assert_array_equal(np.asarray(ua.g), np.asarray(ub.g))


class TestHaloExtend:
    def test_periodic_matches_roll(self, rng):
        shape = (4, 5, 6)
        x = jnp.asarray(rng.normal(size=(2, 120)), jnp.float32)
        ext = halo_extend(x, shape, (0, 0, 0), STENCIL_GRAD_6PT)
        assert ext.shape == (2, 6, 7, 8)
        grid = np.asarray(x).reshape(2, *shape)
        want = np.pad(grid, [(0, 0), (1, 1), (1, 1), (1, 1)], mode="wrap")
        np.testing.assert_array_equal(np.asarray(ext), want)

    def test_ghost_planes_trimmed_to_radius(self, rng):
        """A width-2 caller halo feeding a radius-1 stencil keeps exactly
        one ghost layer (the rest is trimmed, not wrapped)."""
        shape = (4, 4, 4)
        grid = rng.normal(size=(1, 8, 4, 4)).astype(np.float32)   # halo 2 in x
        ext = halo_extend(jnp.asarray(grid.reshape(1, -1)), shape,
                          (2, 0, 0), STENCIL_GRAD_6PT)
        assert ext.shape == (1, 6, 6, 6)
        np.testing.assert_array_equal(np.asarray(ext)[:, :, 1:-1, 1:-1],
                                      grid[:, 1:-1])


class TestCapabilitySurface:
    """The executor-capability contract is public: registration declares
    it, the prologue honours it, misuse fails fast."""

    def test_windowed_is_registered_with_capability(self):
        assert "pallas_windowed" in tdp.list_executors()
        assert tdp.executor_wants("pallas_windowed") == "halo_extended"
        assert tdp.executor_wants("xla") == "gathered"
        assert tdp.get_executor_entry("pallas_windowed").wants == \
            "halo_extended"

    def test_windowed_interpret_spelling_canonicalises(self):
        t = tdp.Target("pallas_windowed_interpret")
        assert t.backend == "pallas_windowed" and t.interpret
        assert t.executor == "pallas_windowed"

    def test_invalid_capability_rejected(self):
        with pytest.raises(ValueError, match="capability"):
            tdp.register_executor("bad_caps", lambda plan, g: g,
                                  wants="telepathic")

    def test_pointwise_spec_rejected_on_capability_executor(self):
        """A wants='halo_extended' executor fed a non-stencil spec is a
        contract violation, caught before any compilation."""
        @tdp.kernel(fields=[tdp.field(2)], out=2)
        def scale(x):
            return 2.0 * x

        with pytest.raises(ValueError, match="halo_extended"):
            tdp.launch(scale, WINDOWED, jnp.ones((2, 8), jnp.float32))
        with pytest.raises(ValueError, match="halo_extended"):
            launch_plan(scale, WINDOWED, lattice=Lattice((2, 4)))

    def test_unfused_sim_rejects_stencil_only_target(self):
        """The unfused pipeline never dispatches a stencil-only executor
        (collision is pointwise, stream/gradients run on the default
        target) — silently benchmarking xla instead must be impossible."""
        with pytest.raises(ValueError, match="stencil-only"):
            BinaryFluidSim((8, 8, 8), target=WINDOWED)
        # fused modes are the supported pairing
        BinaryFluidSim((8, 8, 8), target=WINDOWED, fused="two_launch")

    def test_launch_plan_requires_known_out(self):
        """A spec whose output count is only known from the launched
        array cannot be introspected faithfully — fail, don't guess."""
        spec = tdp.KernelSpec(lambda x: x, fields=(tdp.field(),))
        with pytest.raises(ValueError, match="out"):
            launch_plan(spec, tdp.Target("xla"))

    def test_mock_capability_executor_end_to_end(self, rng):
        """register_executor(..., wants='halo_extended') alone suffices:
        a whole-lattice mock resolves offsets from the extended grid and
        matches xla — zero core edits."""
        def mock(plan, prepared):
            chunks = []
            for x, s in zip(prepared, plan.stencils):
                if s is None:
                    chunks.append(x)
                    continue
                r = s.radius_per_dim()
                nb = []
                for off in s.offsets:
                    g = x
                    for d, (o, rd, sd) in enumerate(zip(off, r, plan.shape)):
                        g = jnp.take(g, jnp.arange(rd + o, rd + o + sd),
                                     axis=d + 1)
                    nb.append(g.reshape(x.shape[0], -1))
                chunks.append(jnp.stack(nb))
            vals = plan.kernel(*chunks, **plan.consts)
            return vals if isinstance(vals, tuple) else (vals,)

        tdp.register_executor("mock_windowed", mock, wants="halo_extended")
        try:
            lat = Lattice((4, 4, 4))
            phi = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
            ga, la = tdp.launch(lbst.GRAD6_SPEC, tdp.Target("mock_windowed"),
                                phi, lattice=lat)
            gb, lb = tdp.launch(lbst.GRAD6_SPEC, "xla", phi, lattice=lat)
            np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        finally:
            tdp.unregister_executor("mock_windowed")

    def test_spec_max_radius_per_dim(self):
        assert lbst.FUSED_SPEC.max_radius_per_dim() == (2, 2, 2)
        assert lbst.STREAM_SPEC.max_radius_per_dim() == (1, 1, 1)
        with pytest.raises(ValueError, match="stencil"):
            tdp.KernelSpec(lambda x: x, fields=(tdp.field(1),),
                           out=1).max_radius_per_dim()


class TestMemoryEstimates:
    """LaunchPlan.hbm_bytes_estimate / vmem_bytes_estimate: the gathered
    path carries the noffsets× term, the windowed path must not."""

    def test_gather_path_has_noffsets_term(self):
        lat = Lattice((16, 16, 16))
        plan = launch_plan(lbst.FUSED_SPEC, tdp.Target("xla", vvl=128),
                           lattice=lat)
        noff = lbst.STENCIL_FUSED_G.noffsets          # 57
        # both stacks materialised: (19 + 57) · 19 rows × nsites
        assert plan.hbm_bytes_estimate() == \
            ((19 + noff) * NVEL + 2 * NVEL) * lat.nsites * 4
        assert plan.vmem_bytes_estimate() == \
            ((19 + noff) * NVEL + 2 * NVEL) * 128 * 4

    def test_windowed_path_has_no_noffsets_term(self):
        """The windowed estimate depends on the stencil *radius* only:
        two stencils of equal radius but 7 vs 19 offsets give the same
        estimate, while the gathered estimates differ by the offset
        count."""
        spec7 = tdp.KernelSpec(lambda p: p[0],
                               fields=(tdp.field(1,
                                                 stencil=STENCIL_GRAD_6PT),),
                               out=1, name="star7")
        spec19 = tdp.KernelSpec(lambda p: p[0],
                                fields=(tdp.field(1,
                                                  stencil=STENCIL_GRAD_19PT),),
                                out=1, name="star19")
        lat = Lattice((16, 16, 16))
        w7 = launch_plan(spec7, WINDOWED, lattice=lat)
        w19 = launch_plan(spec19, WINDOWED, lattice=lat)
        assert w7.hbm_bytes_estimate() == w19.hbm_bytes_estimate()
        assert w7.vmem_bytes_estimate() == w19.vmem_bytes_estimate()
        g7 = launch_plan(spec7, tdp.Target("xla", vvl=64), lattice=lat)
        g19 = launch_plan(spec19, tdp.Target("xla", vvl=64), lattice=lat)
        assert g19.hbm_bytes_estimate() - g7.hbm_bytes_estimate() == \
            (19 - 7) * lat.nsites * 4
        assert g19.vmem_bytes_estimate() - g7.vmem_bytes_estimate() == \
            (19 - 7) * 64 * 4

    def test_windowed_kills_the_amplification(self):
        """The headline: at 64³ the fused gather stack needs ~1.4 GiB,
        the windowed operands stay under 100 MiB (ghost overhead only)."""
        lat = Lattice((64, 64, 64))
        g = launch_plan(lbst.FUSED_SPEC, tdp.Target("xla"), lattice=lat)
        w = launch_plan(lbst.FUSED_SPEC, WINDOWED, lattice=lat)
        assert g.hbm_bytes_estimate() > 1.3 * 2**30
        assert w.hbm_bytes_estimate() < 100 * 2**20
        assert g.hbm_bytes_estimate() / w.hbm_bytes_estimate() > 15

    def test_windowed_vmem_tracks_plane_block(self):
        lat = Lattice((16, 16, 16))
        w1 = launch_plan(lbst.STREAM_SPEC, WINDOWED, lattice=lat)
        w4 = launch_plan(
            lbst.STREAM_SPEC,
            WINDOWED.with_(tuning={"plane_block": 4}), lattice=lat)
        # window depth grows p + 2r: 3 planes → 6 planes of input
        assert w4.vmem_bytes_estimate() > w1.vmem_bytes_estimate()
        # Each block is padded to Mosaic's (8, 128) f32 tile and counted
        # three times (two pipeline buffers + the loaded value): an
        # 18×18 extended plane of 19 components is 19·24·128 words, the
        # p·256-site output block 24·(p·256) words.
        plane, out1 = 19 * 24 * 128 * 4, 24 * 256 * 4
        assert w1.window_blocks() == [(0, 3 * plane), (-1, out1)]
        assert w1.vmem_bytes_estimate() == 3 * (3 * plane + out1)
        # above plane_block=1 the p rows of all 19 offsets are also
        # concatenated into one (19, 19, p·256) chunk
        chunk4 = 19 * 24 * (4 * 256) * 4
        assert w4.vmem_bytes_estimate() == \
            3 * (6 * plane + 4 * out1) + chunk4

    def test_estimates_need_geometry(self):
        plan = launch_plan(tdp.KernelSpec(lambda x: x,
                                          fields=(tdp.field(2),), out=2),
                           tdp.Target("xla", vvl=32))
        with pytest.raises(ValueError, match="lattice"):
            plan.hbm_bytes_estimate()
        # the gathered VMEM rule needs no lattice (pure VVL blocks)
        assert plan.vmem_bytes_estimate() == (2 + 2) * 32 * 4
