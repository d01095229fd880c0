"""The gather-free windowed stencil executor (``pallas_windowed``).

ROADMAP stencil-memory stage (b), pinned here (docs/stencil.md):

* ``pallas_windowed`` (interpret mode on the CPU) matches the ``xla``
  executor under the cross-path contract (``tdp.check_fields``) on every
  LB stencil spec — STREAM, GRAD6, and both fused modes — including the
  10-step fused trajectory at 16³ and caller-supplied ghost planes;
* the executor is registered through the *public*
  ``register_executor(..., wants="halo_extended")`` capability surface:
  a mock capability-declaring executor runs end-to-end with zero core
  edits, and feeding one a pointwise spec fails fast;
* the ``LaunchPlan`` memory models show the ``noffsets×`` HBM term gone:
  the windowed estimate depends only on the stencil *radius*, never on
  its offset count;
* periodic dimensions are wrapped inside the kernel
  (``wraps_periodic=True``): such a launch matches the same launch given
  wrap-filled caller ghosts, and no pad runs outside the kernel;
* a launch whose whole-plane window does not fit the VMEM limit is
  blocked in y (``LaunchPlan.y_block``), and runs the same numbers.
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
from repro.kernels.lb_collision import CV, NVEL
from repro.kernels.tdp_windowed import windowed_execute
from repro.lb import programs as lbp
from repro.lb import stencil as lbst
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim

WINDOWED = tdp.Target("pallas_windowed", interpret=True)
V5E_VMEM_LIMIT = 100 * 2 ** 20


def _rand_f(rng, n):
    return jnp.asarray(0.05 * rng.normal(size=(NVEL, n)) + 1 / 19.,
                       jnp.float32)


def _rand_g(rng, n):
    return jnp.asarray(0.05 * rng.normal(size=(NVEL, n)), jnp.float32)


def _parity_stream(rng, shape, tuning=None):
    lat = Lattice(shape)
    f = _rand_f(rng, lat.nsites)
    got = tdp.launch(lbst.STREAM_SPEC, WINDOWED.with_(tuning=tuning or {}),
                     f, lattice=lat)
    want = tdp.launch(lbst.STREAM_SPEC, tdp.Target("xla", vvl=64), f,
                      lattice=lat)
    return {"f": want}, {"f": got}


def _parity_grad6(rng):
    lat = Lattice((16, 16, 16))
    phi = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
    got = tdp.launch(lbst.GRAD6_SPEC, WINDOWED, phi, lattice=lat)
    want = tdp.launch(lbst.GRAD6_SPEC, tdp.Target("xla", vvl=64), phi,
                      lattice=lat)
    return dict(zip(("grad", "lap"), want)), dict(zip(("grad", "lap"), got))


def _parity_fused_step(rng, mode):
    from repro.kernels import ops
    lat = Lattice((16, 16, 16))
    f, g = _rand_f(rng, lat.nsites), _rand_g(rng, lat.nsites)
    got = ops.lb_fused_step(f, g, grid_shape=lat.shape, mode=mode,
                            target=WINDOWED)
    want = ops.lb_fused_step(f, g, grid_shape=lat.shape, mode=mode,
                             backend="xla", vvl=64)
    return dict(zip("fg", want)), dict(zip("fg", got))


def _parity_ghost_halo(rng):
    """Caller-filled ghost planes (the sharded contract, width 2 for the
    radius-2 fused neighbourhood) reproduce the periodic gather."""
    from repro.kernels import ops
    shape = (8, 8, 8)
    f, g = _rand_f(rng, 512), _rand_g(rng, 512)

    def ext2(x):
        x = np.asarray(x).reshape(NVEL, *shape)
        return jnp.asarray(np.concatenate([x[:, -2:], x, x[:, :2]], axis=1)
                           .reshape(NVEL, -1))

    got = ops.lb_fused_step(ext2(f), ext2(g), grid_shape=shape,
                            halo=(2, 0, 0), mode="one_launch",
                            target=WINDOWED)
    want = ops.lb_fused_step(f, g, grid_shape=shape, mode="one_launch",
                             backend="xla", vvl=64)
    return dict(zip("fg", want)), dict(zip("fg", got))


def _parity_fused_trajectory(rng):
    """10 fused steps of a 16³ spinodal quench."""
    p = LBParams(A=0.125, B=0.125, kappa=0.02)
    a = BinaryFluidSim((16, 16, 16), params=p, fused="one_launch")
    b = BinaryFluidSim((16, 16, 16), params=p, fused="one_launch",
                       target=WINDOWED)
    st0 = a.init_spinodal(seed=3, noise=0.05)
    ua, ub = a.step(st0, 10), b.step(st0, 10)
    return {"f": ua.f, "g": ua.g}, {"f": ub.f, "g": ub.g}


class TestWindowedParity:
    """``pallas_windowed`` against the xla executor on the single-source
    LB specs, under the cross-path contract (:func:`tdp.check_fields`):
    the portability contract extended to the gather-free path."""

    CASES = {
        "stream-16x16x16": lambda rng: _parity_stream(rng, (16, 16, 16)),
        "stream-5x4x3": lambda rng: _parity_stream(rng, (5, 4, 3)),
        "grad6": _parity_grad6,
        "fused-one_launch": lambda rng: _parity_fused_step(rng,
                                                           "one_launch"),
        "fused-two_launch": lambda rng: _parity_fused_step(rng,
                                                           "two_launch"),
        # plane_block > 1 (and X not a multiple of it) only changes the
        # TLP chunking
        "plane_block-2": lambda rng: _parity_stream(rng, (7, 4, 5),
                                                    {"plane_block": 2}),
        "plane_block-3": lambda rng: _parity_stream(rng, (7, 4, 5),
                                                    {"plane_block": 3}),
        "ghost-halo": _parity_ghost_halo,
        "fused-trajectory-10-steps": _parity_fused_trajectory,
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_matches_xla(self, rng, case):
        want, got = self.CASES[case](rng)
        tdp.check_fields(want, got)


#: a 2-D nine-point neighbourhood (radius 1) and a kernel reading all of it
_STENCIL_2D = Stencil("box_2d", tuple((dx, dy) for dx in (-1, 0, 1)
                                      for dy in (-1, 0, 1)))
_BOX2D_SPEC = tdp.KernelSpec(
    lambda nb: sum((i + 1.0) * nb[i] for i in range(9)),
    fields=(tdp.field(2, stencil=_STENCIL_2D),), out=2, name="box_2d")

_FUSED_CONSTS = {k: v for k, v in lbp.collision_consts(
    **LBParams(A=0.125, B=0.125, kappa=0.02).as_kwargs()).items()
    if k in lbst.FUSED_SPEC.consts}


def _mixed_body(f_nb, rho, idx, *, alpha, w):
    acc = (f_nb * w.reshape(-1, 1, 1)).sum(axis=0)
    return alpha * acc + rho + (idx % 5).astype(acc.dtype), acc[:1] - rho


#: a stencil field, a pointwise field, array consts and the site index
_MIXED_SPEC = tdp.KernelSpec(
    _mixed_body, fields=(tdp.field(2, stencil=STENCIL_GRAD_6PT),
                         tdp.field(1)),
    out=(2, 1), site_index=True, consts=("alpha", "w"), name="mixed_yb")


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
    the loaded planes.  A launch matches the same launch handed
    wrap-filled ghost planes in every dimension (nothing wrapped
    in-kernel) under the cross-path contract."""

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
        tdp.check_fields(launch(r), launch(halo))

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


def _blocked_plan(spec, shape, y_block, halo=None, consts=None,
                  tuning=None):
    """The plan a device whose VMEM limit just holds a ``y_block``-row
    window would build: the limit is passed to ``launch_plan``."""
    target = WINDOWED.with_(tuning=tuning or {})
    lat = Lattice(shape)
    whole = tdp.launch_plan(spec, target, lattice=lat, halo=halo,
                            consts=consts)
    assert whole.y_block is None
    cap = whole._replace(y_block=y_block).vmem_bytes_estimate()
    assert cap < whole.vmem_bytes_estimate()
    plan = tdp.launch_plan(spec, target, lattice=lat, halo=halo,
                           consts=consts, vmem_limit=cap)
    assert plan.y_block == y_block
    return plan


def _run_plan(plan, *prepared):
    return jax.jit(lambda *a: windowed_execute(plan, a))(*prepared)


def _fg(rng, shape):
    n = int(np.prod(shape))
    return _rand_f(rng, n), _rand_g(rng, n)


class TestYBlockedWindow:
    """A window that does not fit the VMEM limit is cut into y-blocks of
    ``y_block`` rows (plus 8 halo rows on either side), each a step of a
    second grid axis; periodic y wraps in the halo blocks' index."""

    SHAPE = (3, 48, 8)

    def test_fused_one_launch_step_matches_xla(self, rng):
        plan = _blocked_plan(lbst.FUSED_SPEC, self.SHAPE, 16,
                             consts=_FUSED_CONSTS)
        f, g = _fg(rng, self.SHAPE)
        got = _run_plan(plan, f.reshape(NVEL, *self.SHAPE),
                        g.reshape(NVEL, *self.SHAPE))
        want = tdp.launch(lbst.FUSED_SPEC, tdp.Target("xla", vvl=64), f, g,
                          lattice=Lattice(self.SHAPE), consts=_FUSED_CONSTS)
        tdp.check_fields(dict(zip("fg", want)), dict(zip("fg", got)))

    def test_fused_five_step_trajectory_matches_xla(self, rng):
        plan = _blocked_plan(lbst.FUSED_SPEC, self.SHAPE, 8,
                             consts=_FUSED_CONSTS)
        lat = Lattice(self.SHAPE)
        step = jax.jit(lambda f, g: windowed_execute(
            plan, (f.reshape(NVEL, *self.SHAPE),
                   g.reshape(NVEL, *self.SHAPE))))
        ref = jax.jit(lambda f, g: tdp.launch(
            lbst.FUSED_SPEC, tdp.Target("xla", vvl=64), f, g, lattice=lat,
            consts=_FUSED_CONSTS))
        a = b = _fg(rng, self.SHAPE)
        for _ in range(5):
            a, b = step(*a), ref(*b)
        tdp.check_fields(dict(zip("fg", b)), dict(zip("fg", a)))

    @pytest.mark.parametrize("case", ["fused", "pointwise-site-index-p2"])
    def test_y_block_not_dividing_y(self, rng, case):
        """Y = 44 has no divisor that is a multiple of 8: the blocks run
        past Y to 48, the rows laid out once so the halo blocks still
        wrap, and the rows past Y are cut from the output."""
        shape = (3, 44, 8)
        lat = Lattice(shape)
        if case == "fused":
            spec, consts, tuning = lbst.FUSED_SPEC, _FUSED_CONSTS, {}
        else:
            spec, tuning = _MIXED_SPEC, {"plane_block": 2}
            consts = {"alpha": 1.5,
                      "w": jnp.asarray(rng.normal(size=7), jnp.float32)}
        plan = _blocked_plan(spec, shape, 16, consts=consts, tuning=tuning)
        xs = _launch_inputs(spec, shape, rng)
        prepared = [x.reshape(x.shape[0], *shape) if fs.stencil is not None
                    else x for x, fs in zip(xs, spec.fields)]
        got = _run_plan(plan, *prepared)
        want = tdp.launch(spec, tdp.Target("xla", vvl=64), *xs, lattice=lat,
                          consts=consts)
        want = want if isinstance(want, tuple) else (want,)
        tdp.check_fields(want, got)

    def test_y_wraps_across_first_and_last_block(self, rng):
        """Streaming moves data only, so it is exact: row 0 of the first
        block pulls from row Y−1 of the last, and row Y−1 from row 0."""
        shape = (2, 32, 8)
        plan = _blocked_plan(lbst.STREAM_SPEC, shape, 8)
        f = np.asarray(_rand_f(rng, int(np.prod(shape))))
        (got,) = _run_plan(plan, jnp.asarray(f).reshape(NVEL, *shape))
        got = np.asarray(got).reshape(NVEL, *shape)
        fg = f.reshape(NVEL, *shape)
        want = np.stack([np.roll(fg[q], tuple(int(c) for c in CV[q]),
                                 axis=(0, 1, 2)) for q in range(NVEL)])
        np.testing.assert_array_equal(got, want)
        for q in range(NVEL):
            cy = int(CV[q][1])
            if cy:
                edge = 0 if cy > 0 else shape[1] - 1
                src = fg[q][:, (edge - cy) % shape[1]]
                np.testing.assert_array_equal(
                    got[q, :, edge],
                    np.roll(src, (int(CV[q][0]), int(CV[q][2])), axis=(0, 1)))

    @pytest.mark.parametrize("shape", [(3, 48, 8), (3, 44, 8)])
    def test_ghost_y_matches_unblocked_launch(self, rng, shape):
        """The pencil's geometry (exchanged ghosts in x and y, z wrapped
        in-kernel): the blocked window reads the caller's ghost rows,
        and agrees with the whole-plane launch of the same arrays."""
        halo = (2, 2, 0)
        plan = _blocked_plan(lbst.FUSED_SPEC, shape, 16, halo=halo,
                             consts=_FUSED_CONSTS)
        assert plan.wrap_dims == ((2,), (2,))
        f, g = _fg(rng, shape)
        fe, ge = (_with_ghosts(x, shape, halo) for x in (f, g))
        want = tdp.launch(lbst.FUSED_SPEC, WINDOWED, fe, ge,
                          lattice=Lattice(shape), halo=halo,
                          consts=_FUSED_CONSTS)

        def prepared(x, s):
            r = s.radius_per_dim()
            grid = x.reshape(NVEL, *(n + 2 * h for n, h in zip(shape, halo)))
            return grid[:, halo[0] - r[0]:grid.shape[1] - halo[0] + r[0],
                        halo[1] - r[1]:grid.shape[2] - halo[1] + r[1],
                        halo[2]:grid.shape[3] - halo[2]]
        got = _run_plan(plan, *(prepared(x, fs.stencil) for x, fs in
                                zip((fe, ge), lbst.FUSED_SPEC.fields)))
        tdp.check_fields(dict(zip("fg", want)), dict(zip("fg", got)))

    def test_refused_when_the_smallest_block_does_not_fit(self, rng):
        """A 512-lane z extent overflows the 16 MiB interpret limit even
        in 8-row blocks: the launch is refused at plan build, naming the
        block."""
        shape = (2, 16, 512)
        lat = Lattice(shape)
        plan = tdp.launch_plan(lbst.FUSED_SPEC, WINDOWED, lattice=lat,
                               consts=_FUSED_CONSTS)
        assert plan.y_block == 8
        f, g = _fg(rng, shape)
        with pytest.raises(tdp.WindowVmemError, match="y_block=8"):
            tdp.launch(lbst.FUSED_SPEC, WINDOWED, f, g, lattice=lat,
                       consts=_FUSED_CONSTS)


def _index_body(nb, idx):
    return nb[0] + nb[1], nb[2, :1] + idx.astype(nb.dtype)


#: two outputs of different widths, one carrying the site index; sums
#: only, so every executor computes them bit for bit alike
_INDEX_SPEC = tdp.KernelSpec(
    _index_body, fields=(tdp.field(2, stencil=STENCIL_GRAD_6PT),),
    out=(2, 1), site_index=True, name="index_sum")


class TestOutputStore:
    """Outputs are stored in the lattice's grid layout where their blocks'
    rows are whole sublane tiles, flat (``_flat`` in the kernel's name)
    where they are not; either way every site lands where the xla
    executor puts it (held to it under the cross-path contract)."""

    CASES = {
        # name: (spec, shape, caller ghosts, plane_block, y_block, form)
        "grid-p1": (lbst.STREAM_SPEC, (16, 16, 16), None, 1, None, "grid"),
        "grid-p2-x-pad": (lbst.STREAM_SPEC, (15, 16, 16), None, 2, None,
                          "grid"),
        "grid-site-index": (_INDEX_SPEC, (15, 16, 16), None, 2, None,
                            "grid"),
        "y-blocked-p2-past-y": (lbst.STREAM_SPEC, (5, 44, 16), None, 2, 16,
                                "grid"),
        "y-blocked-site-index": (_INDEX_SPEC, (5, 44, 16), None, 2, 16,
                                 "grid"),
        "flat-ghost-rows": (lbst.STREAM_SPEC, (6, 10, 16), (1, 1, 0), 1,
                            None, "flat"),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_bit_identical_to_xla(self, rng, case):
        spec, shape, halo, p, y_block, form = self.CASES[case]
        lat = Lattice(shape)
        xs = [_with_ghosts(x, shape, halo) if halo else x
              for x in _launch_inputs(spec, shape, rng)]
        want = tdp.launch(spec, tdp.Target("xla", vvl=64), *xs, lattice=lat,
                          halo=halo)
        tuning = {"plane_block": p}
        if y_block:
            plan = _blocked_plan(spec, shape, y_block, tuning=tuning)
            xs = [x.reshape(x.shape[0], *shape) for x in xs]

            def fn(*a):
                return windowed_execute(plan, a)
        else:
            target = WINDOWED.with_(tuning=tuning)

            def fn(*a):
                return tdp.launch(spec, target, *a, lattice=lat, halo=halo)
        text = jax.jit(fn).lower(*xs).as_text(debug_info=True)
        name = (f"tdp_windowed_{spec.name}_p{p}"
                f"{f'_yb{y_block}' if y_block else ''}"
                f"{'_flat' if form == 'flat' else ''}_soa")
        assert name in text
        assert ("_flat_" in text) == (form == "flat")
        got = jax.jit(fn)(*xs)
        got = got if isinstance(got, tuple) else (got,)
        want = want if isinstance(want, tuple) else (want,)
        tdp.check_fields(want, got)


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
            tdp.check_fields({"grad": gb, "lap": lb}, {"grad": ga, "lap": la})
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
        one = launch_plan(lbst.FUSED_SPEC, target, lattice=lat,
                          vmem_limit=V5E_VMEM_LIMIT)
        assert one.wrap_dims == ((0, 1, 2), (0, 1, 2))
        # operands are the caller's arrays, reshaped: f, g in, f', g' out
        assert one.hbm_bytes_estimate() == 4 * NVEL * n * 4
        pencil = launch_plan(lbst.FUSED_SPEC, target, lattice=lat,
                             halo=(2, 2, 0), vmem_limit=V5E_VMEM_LIMIT)
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

    def test_y_blocked_window_counts_its_halo_rows(self):
        """128×256×256 on a v5e: the whole-plane window (19 × 256² planes)
        exceeds the limit, the plan blocks y at 128 rows, and each window
        plane spans 128 + 2·8 rows; in HBM each block re-reads 16 rows."""
        lat = Lattice((128, 256, 256))
        plan = launch_plan(lbst.FUSED_SPEC, tdp.Target("pallas_windowed"),
                           lattice=lat, vmem_limit=V5E_VMEM_LIMIT)
        assert plan.y_block == 128
        tile, out = 19 * 144 * 256 * 4, 24 * 128 * 256 * 4
        assert plan.window_blocks() == [(0, 3 * tile), (1, 5 * tile),
                                        (-1, out), (-1, out)]
        assert plan.vmem_bytes_estimate() == 3 * (8 * tile + 2 * out)
        assert plan.vmem_bytes_estimate() <= V5E_VMEM_LIMIT < \
            plan._replace(y_block=None).vmem_bytes_estimate()
        read = 2 * NVEL * 128 * (2 * 144) * 256
        assert plan.hbm_bytes_estimate() == (read + 2 * NVEL * lat.nsites) * 4

    def test_estimates_need_geometry(self):
        plan = launch_plan(tdp.KernelSpec(lambda x: x,
                                          fields=(tdp.field(2),), out=2),
                           tdp.Target("xla", vvl=32))
        with pytest.raises(ValueError, match="lattice"):
            plan.hbm_bytes_estimate()
        # the gathered VMEM rule needs no lattice (pure VVL blocks)
        assert plan.vmem_bytes_estimate() == (2 + 2) * 32 * 4
