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
  its offset count;
* periodic dimensions are wrapped inside the kernel
  (``wraps_periodic=True``): such a launch is **bit-identical** to the
  same launch given wrap-filled caller ghosts, and no pad runs outside
  the kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tdp
from repro.core import (
    Lattice,
    STENCIL_GRAD_6PT,
    STENCIL_GRAD_19PT,
    Stencil,
    halo_extend,
    launch_plan,
)
from repro.kernels.lb_collision import NVEL
from repro.lb import programs as lbp
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


#: a 2-D nine-point neighbourhood (radius 1) and a kernel reading all of it
_STENCIL_2D = Stencil("box_2d", tuple((dx, dy) for dx in (-1, 0, 1)
                                      for dy in (-1, 0, 1)))
_BOX2D_SPEC = tdp.KernelSpec(
    lambda nb: sum((i + 1.0) * nb[i] for i in range(9)),
    fields=(tdp.field(2, stencil=_STENCIL_2D),), out=2, name="box_2d")

_FUSED_CONSTS = {k: v for k, v in lbp.collision_consts(
    **LBParams(A=0.125, B=0.125, kappa=0.02).as_kwargs()).items()
    if k in lbst.FUSED_SPEC.consts}


def _with_ghosts(x, shape, halo):
    """``(ncomp, nsites)`` → the same field carrying ``halo[d]`` wrap-filled
    ghost layers per dimension, flattened: what a caller with exchanged
    ghost planes would pass."""
    grid = x.reshape(x.shape[0], *shape)
    ext = jnp.pad(grid, [(0, 0)] + [(h, h) for h in halo], mode="wrap")
    return ext.reshape(x.shape[0], -1)


def _launch_inputs(spec, shape, rng):
    n = int(np.prod(shape))
    return [jnp.asarray(0.05 * rng.normal(size=(fs.ncomp, n)) + 1 / 19.,
                        jnp.float32) for fs in spec.fields]


def _pads_outside_kernel(jaxpr) -> int:
    """Equations of a launch's jaxpr that can build a padded copy (``pad``,
    or the ``concatenate`` that ``jnp.pad(mode="wrap")`` becomes), not
    counting the Pallas kernel body."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pad", "concatenate"):
            n += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pads_outside_kernel(sub)
    return n


class TestPeriodicInKernel:
    """``pallas_windowed`` wraps periodic dimensions inside the kernel:
    x through the window's BlockSpec index modulo X, y and z by rotating
    the loaded planes.  Pure data movement, so a launch is bit-identical
    to the same launch handed wrap-filled ghost planes in every
    dimension (nothing wrapped in-kernel)."""

    CASES = {
        # name: (spec, shape, in-kernel-wrapping launch halo, tuning, layout)
        "3d-r1": (lbst.STREAM_SPEC, (5, 4, 6), (0, 0, 0), {}, "soa"),
        "3d-r2-fused": (lbst.FUSED_SPEC, (4, 4, 4), (0, 0, 0), {}, "soa"),
        "pencil-r1": (lbst.STREAM_SPEC, (5, 4, 6), (1, 2, 0), {}, "soa"),
        "pencil-r2-fused": (lbst.FUSED_SPEC, (4, 4, 4), (2, 3, 0), {},
                            "soa"),
        "2d": (_BOX2D_SPEC, (6, 5), (0, 0), {}, "soa"),
        "plane-block-1": (lbst.GRAD6_SPEC, (7, 4, 5), (0, 0, 0),
                          {"plane_block": 1}, "soa"),
        "plane-block-3-of-7": (lbst.STREAM_SPEC, (7, 4, 5), (0, 0, 0),
                               {"plane_block": 3}, "soa"),
        "thinnest-r2": (lbst.FUSED_SPEC, (2, 2, 2), (0, 0, 0), {}, "soa"),
        "aosoa": (lbst.STREAM_SPEC, (4, 4, 8), (0, 0, 0), {}, "aosoa"),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_bit_identical_to_caller_ghosts(self, rng, case):
        spec, shape, halo, tuning, layout = self.CASES[case]
        lat = Lattice(shape)
        target = tdp.Target("pallas_windowed", interpret=True,
                            layout=layout, vvl=16, tuning=tuning)
        consts = _FUSED_CONSTS if spec.consts else {}
        r = spec.max_radius_per_dim()
        xs = _launch_inputs(spec, shape, rng)

        def launch(h):
            args = [_with_ghosts(x, shape, h) if fs.stencil is not None
                    else x for x, fs in zip(xs, spec.fields)]
            out = tdp.launch(spec, target, *args, lattice=lat,
                             halo=h if any(h) else None, consts=consts)
            return out if isinstance(out, tuple) else (out,)

        wraps = tdp.launch_plan(spec, target, lattice=lat,
                                halo=halo if any(halo) else None)
        assert all(w == tuple(d for d, h in enumerate(halo) if h == 0)
                   for w, fs in zip(wraps.wrap_dims, spec.fields)
                   if fs.stencil is not None)
        got, want = launch(halo), launch(r)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("halo", [(0, 0, 0), (1, 1, 0)])
    def test_no_pad_outside_the_kernel(self, rng, halo):
        """A periodic lattice reaches the kernel as a reshape, a pencil
        as its ghost planes trimmed to the radius: no ``pad`` runs in
        the launch outside the kernel (the wrap pad of
        :func:`halo_extend` is gone)."""
        shape = (4, 4, 6)
        lat = Lattice(shape)
        f = _with_ghosts(_launch_inputs(lbst.STREAM_SPEC, shape, rng)[0],
                         shape, halo)
        h = halo if any(halo) else None
        jaxpr = jax.make_jaxpr(lambda x: tdp.launch(
            lbst.STREAM_SPEC, WINDOWED, x, lattice=lat, halo=h))(f)
        assert _pads_outside_kernel(jaxpr.jaxpr) == 0
        # the padding prologue a plain halo_extended executor gets
        # copies the field to wrap its periodic dims
        jaxpr = jax.make_jaxpr(lambda x: halo_extend(
            x, shape, halo, lbst.STENCIL_D3Q19_PULL))(f)
        assert _pads_outside_kernel(jaxpr.jaxpr) > 0


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
        assert tdp.get_executor_entry("pallas_windowed").wraps_periodic
        assert not tdp.get_executor_entry("xla").wraps_periodic

    def test_wraps_periodic_needs_a_grid(self):
        """Only a halo_extended executor receives a grid it could wrap."""
        with pytest.raises(ValueError, match="wraps_periodic"):
            tdp.register_executor("bad_wrap", lambda plan, g: g,
                                  wraps_periodic=True)
        assert "bad_wrap" not in tdp.list_executors()

    @pytest.mark.parametrize("wraps", [False, True])
    def test_prologue_honours_wraps_periodic(self, rng, wraps):
        """The capability alone decides what the prologue hands over: a
        wrap-padded grid, or the interior extent in every periodic dim
        (ghost dims trimmed to the radius either way)."""
        seen = []

        def spy(plan, prepared):
            seen.append((plan.wrap_dims, tuple(prepared[0].shape)))
            return (jnp.zeros((1, 120), jnp.float32),)

        tdp.register_executor("spy_windowed", spy, wants="halo_extended",
                              wraps_periodic=wraps)
        try:
            shape, halo = (4, 5, 6), (2, 0, 0)      # ghosts in x only
            x = jnp.ones((1, 8 * 5 * 6), jnp.float32)
            spec = tdp.KernelSpec(
                lambda p: p[0], out=1, name="star",
                fields=(tdp.field(1, stencil=STENCIL_GRAD_6PT),))
            tdp.launch(spec, tdp.Target("spy_windowed"), x,
                       lattice=Lattice(shape), halo=halo)
        finally:
            tdp.unregister_executor("spy_windowed")
        want = (((1, 2),), (1, 6, 5, 6)) if wraps else (((),),
                                                         (1, 6, 7, 8))
        assert seen == [want]

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
        # three times (two pipeline buffers + the loaded value): a
        # periodic 16×16 plane of 19 components, wrapped in-kernel with
        # no ghosts, is 19·16·128 words, the p·256-site output block
        # 24·(p·256) words.
        plane, out1 = 19 * 16 * 128 * 4, 24 * 256 * 4
        assert w1.window_blocks() == [(0, 3 * plane), (-1, out1)]
        assert w1.vmem_bytes_estimate() == 3 * (3 * plane + out1)
        # above plane_block=1 the p rows of all 19 offsets are also
        # concatenated into one (19, 19, p·256) chunk
        chunk4 = 19 * 24 * (4 * 256) * 4
        assert w4.vmem_bytes_estimate() == \
            3 * (6 * plane + 4 * out1) + chunk4

    def test_wrapped_dims_are_counted_unpadded(self):
        """A dim wrapped in-kernel carries no ghost layers in the
        estimates; a dim with caller ghosts carries the stencil radius
        of them (128³ fused step: f radius 1, g radius 2)."""
        lat = Lattice((128, 128, 128))
        n = lat.nsites
        target = tdp.Target("pallas_windowed")
        one = launch_plan(lbst.FUSED_SPEC, target, lattice=lat)
        assert one.wrap_dims == ((0, 1, 2), (0, 1, 2))
        # operands are the caller's arrays, reshaped: f, g in, f', g' out
        assert one.hbm_bytes_estimate() == 4 * NVEL * n * 4
        pencil = launch_plan(lbst.FUSED_SPEC, target, lattice=lat,
                             halo=(2, 2, 0))
        assert pencil.wrap_dims == ((2,), (2,))
        assert pencil.hbm_bytes_estimate() == 4 * NVEL * (
            2 * n + 130 * 130 * 128 + 132 * 132 * 128)
        # window planes: (19, 1, 128, 128) periodic, (19, 1, 130|132,
        # 128) on the pencil (rows tiled to 136); the kernel's output
        # blocks are (19, 128²), rows tiled to 24
        tile, out = 19 * 128 * 128 * 4, 24 * 128 * 128 * 4
        assert one.window_blocks() == [(0, 3 * tile), (1, 5 * tile),
                                       (-1, out), (-1, out)]
        assert pencil.window_blocks() == [
            (0, 3 * 19 * 136 * 128 * 4), (1, 5 * 19 * 136 * 128 * 4),
            (-1, out), (-1, out)]

    def test_estimates_need_geometry(self):
        plan = launch_plan(tdp.KernelSpec(lambda x: x,
                                          fields=(tdp.field(2),), out=2),
                           tdp.Target("xla", vvl=32))
        with pytest.raises(ValueError, match="lattice"):
            plan.hbm_bytes_estimate()
        # the gathered VMEM rule needs no lattice (pure VVL blocks)
        assert plan.vmem_bytes_estimate() == (2 + 2) * 32 * 4
