"""``tdp.Program`` — declarative multi-launch step graphs.

Pins the redesign's contracts:

* **construction validation** — name dataflow (read-before-write, dead
  intermediates, ncomp consistency) fails fast, before any compilation;
* **the halo schedule** — back-propagated ghost requirements match the
  hand-derived widths of every LB step shape (one exchange round per
  field per step);
* **agreement with the pre-Program driver** — Program trajectories
  (10 steps @16³) match the pre-Program ``BinaryFluidSim`` step sequences
  (reconstructed here from the same jitted launch pipeline the old
  driver hard-wired) across ``xla``, ``pallas_interpret`` and
  ``pallas_windowed_interpret`` under the cross-path contract
  (``tdp.check_fields``), and the python-loop :meth:`step` path is
  bit-identical to the :meth:`run`/``lax.scan`` path;
* **per-stage target routing** — pointwise stages under a stencil-only
  target dispatch to xla, stencil stages keep the target;
* **plan aggregation** — ``Program.plan(target)`` sums the per-stage
  HBM models (gather-free under the windowed executor) and maxes VMEM;
* **deprecation shims** — ``core/execute.py``'s ``launch`` /
  ``launch_stencil`` warn exactly once per call site.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tdp
from repro.core import Lattice, STENCIL_GRAD_6PT
from repro.kernels import ops
from repro.kernels.lb_collision import NVEL
from repro.lb import programs as lbp
from repro.lb import stencil as lbst
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim

GRID = (16, 16, 16)
N = int(np.prod(GRID))
PARAMS = LBParams(A=0.125, B=0.125, kappa=0.02)
WINDOWED = tdp.Target("pallas_windowed", interpret=True)

OPEN_X = (True, False, False)


# ---------------------------------------------------------------------------
# toy specs for construction tests
# ---------------------------------------------------------------------------

@tdp.kernel(fields=[tdp.field(2)], out=2)
def double2(x):
    return 2.0 * x


@tdp.kernel(fields=[tdp.field(1, stencil=STENCIL_GRAD_6PT)], out=1)
def star_sum(p):
    acc = p[0, 0]
    for i in range(1, 7):
        acc = acc + p[i, 0]
    return acc[None]


class TestConstruction:
    def test_unknown_read_name(self):
        with pytest.raises(ValueError, match="unknown name 'b'"):
            tdp.program("p", [tdp.stage(double2, reads="b", writes="a")],
                        fields=("a",))

    def test_read_before_write(self):
        with pytest.raises(ValueError, match="before any stage writes"):
            tdp.program("p", [
                tdp.stage(double2, reads="tmp", writes="tmp"),
                tdp.stage(double2, reads="a", writes="a"),
            ], fields=("a",), intermediates=("tmp",))

    def test_dead_intermediate(self):
        with pytest.raises(ValueError, match="written but never read"):
            tdp.program("p", [tdp.stage(double2, reads="a", writes="tmp")],
                        fields=("a",))

    def test_declared_intermediates_must_match(self):
        with pytest.raises(ValueError, match="intermediates"):
            tdp.program("p", [tdp.stage(double2, reads="a", writes="a")],
                        fields=("a",), intermediates=("ghost",))

    def test_ncomp_conflict(self):
        with pytest.raises(ValueError, match="inconsistent ncomp"):
            tdp.program("p", [
                tdp.stage(double2, reads="a", writes="b"),        # b: 2
                tdp.stage(star_sum, reads="b", writes="a"),       # b: 1
            ], fields=("a", "b"))

    def test_spec_without_out_rejected(self):
        anon = tdp.KernelSpec(lambda x: x, fields=(tdp.field(1),))
        with pytest.raises(ValueError, match="declare out="):
            tdp.stage(anon, reads="a", writes="a")

    def test_binding_arity_mismatch(self):
        with pytest.raises(ValueError, match="read"):
            tdp.stage(double2, reads=("a", "b"), writes="c")
        with pytest.raises(ValueError, match="write"):
            tdp.stage(double2, reads="a", writes=("c", "d"))

    def test_duplicate_fields(self):
        with pytest.raises(ValueError, match="duplicate"):
            tdp.program("p", [tdp.stage(double2, reads="a", writes="a")],
                        fields=("a", "a"))

    def test_needs_at_least_one_stage(self):
        with pytest.raises(ValueError, match="at least one stage"):
            tdp.program("p", [], fields=("a",))


class TestHaloSchedule:
    """The one-exchange-per-step schedule, against hand-derived widths."""

    def consts(self):
        return lbp.collision_consts(**PARAMS.as_kwargs())

    def test_one_launch(self):
        w, geo = lbp.fused_program("one_launch", self.consts()).schedule(
            3, OPEN_X)
        # single radius-2 stage: both fields exchanged at the launch halo
        assert w == {"f": (2, 0, 0), "g": (2, 0, 0)}
        assert geo == [((0, 0, 0), (2, 0, 0))]

    def test_two_launch(self):
        w, geo = lbp.fused_program("two_launch", self.consts()).schedule(
            3, OPEN_X)
        # launch A recomputes the streamed-φ ghost ring locally (ext_out
        # 1) from g's width-2 exchange; f needs only launch B's radius.
        assert w == {"f": (1, 0, 0), "g": (2, 0, 0)}
        assert geo == [((1, 0, 0), (1, 0, 0)), ((0, 0, 0), (1, 0, 0))]

    def test_unfused(self):
        w, geo = lbp.unfused_step_program(self.consts()).schedule(3, OPEN_X)
        # moments recompute φ on a 2-ring, collide on a 1-ring: the old
        # driver's three exchange rounds (φ, f', g') collapse into one
        # {f: 1, g: 2} round at step start.
        assert w == {"f": (1, 0, 0), "g": (2, 0, 0)}
        exts = [e[0] for e, _ in geo]
        halos = [h[0] for _, h in geo]
        assert exts == [2, 1, 1, 0, 0]     # moments, grads, collide, streams
        assert halos == [0, 1, 0, 1, 1]

    def test_closed_dims_need_nothing(self):
        w, geo = lbp.fused_program("one_launch", self.consts()).schedule(
            3, (False, False, False))
        assert all(v == (0, 0, 0) for v in w.values())
        assert geo == [((0, 0, 0), (0, 0, 0))]


# ---------------------------------------------------------------------------
# PR 3 reconstruction: the pre-Program BinaryFluidSim step pipeline,
# jitted exactly as the old driver built it.
# ---------------------------------------------------------------------------

def _pr3_fns(target, pw_target, mode):
    def collide_flat(f, g, phi, gp, d2):
        fo, go = ops.lb_collision(
            f.reshape(NVEL, N), g.reshape(NVEL, N), phi.reshape(1, N),
            gp.reshape(3, N), d2.reshape(1, N), target=pw_target,
            **PARAMS.as_kwargs())
        return fo.reshape(NVEL, *GRID), go.reshape(NVEL, *GRID)

    @jax.jit
    def step_local(f, g):
        phi = g.sum(0)
        gp, d2 = lbst.gradients(phi)
        f, g = collide_flat(f, g, phi, gp, d2)
        return lbst.stream(f), lbst.stream(g)

    @jax.jit
    def collide_local(f, g):
        phi = g.sum(0)
        gp, d2 = lbst.gradients(phi)
        return collide_flat(f, g, phi, gp, d2)

    @jax.jit
    def fused_local(f, g):
        fo, go = ops.lb_fused_step(
            f.reshape(NVEL, N), g.reshape(NVEL, N), grid_shape=GRID,
            mode=mode, target=target, **PARAMS.as_kwargs())
        return fo.reshape(NVEL, *GRID), go.reshape(NVEL, *GRID)

    @jax.jit
    def stream_local(f, g):
        return lbst.stream(f), lbst.stream(g)

    return step_local, collide_local, fused_local, stream_local


def _pr3_trajectory(st, nsteps, target, pw_target, mode):
    step_l, collide_l, fused_l, stream_l = _pr3_fns(target, pw_target,
                                                    mode or "one_launch")
    f, g = st.f, st.g
    if mode:
        f, g = collide_l(f, g)
        for _ in range(nsteps - 1):
            f, g = fused_l(f, g)
        return stream_l(f, g)
    for _ in range(nsteps):
        f, g = step_l(f, g)
    return f, g


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.fixture(scope="module")
def spinodal_state():
    return BinaryFluidSim(GRID, params=PARAMS).init_spinodal(seed=3,
                                                             noise=0.05)


class TestTrajectoryBitIdentity:
    """The acceptance pin: Program trajectories over 10 steps @16³ match
    the pre-Program driver (other jit boundaries, so other fusions) on
    every executor under the cross-path contract, and the scanned path
    is bit-identical to the python loop (one program, driven two
    ways)."""

    CASES = [
        ("xla", tdp.Target("xla", vvl=128), tdp.Target("xla", vvl=128),
         False),
        ("xla", tdp.Target("xla", vvl=128), tdp.Target("xla", vvl=128),
         "one_launch"),
        ("xla", tdp.Target("xla", vvl=128), tdp.Target("xla", vvl=128),
         "two_launch"),
        ("pallas_interpret", tdp.Target("pallas_interpret", vvl=128),
         tdp.Target("pallas_interpret", vvl=128), False),
        ("pallas_interpret", tdp.Target("pallas_interpret", vvl=128),
         tdp.Target("pallas_interpret", vvl=128), "one_launch"),
        ("pallas_interpret", tdp.Target("pallas_interpret", vvl=128),
         tdp.Target("pallas_interpret", vvl=128), "two_launch"),
        # the old driver routed the windowed sim's pointwise prologue to
        # xla (the capability fallback Program now applies per stage)
        ("pallas_windowed_interpret", WINDOWED,
         tdp.Target("xla", vvl=128), "one_launch"),
        ("pallas_windowed_interpret", WINDOWED,
         tdp.Target("xla", vvl=128), "two_launch"),
    ]

    @pytest.mark.parametrize("name,target,pw,mode",
                             CASES, ids=[f"{c[0]}-{c[3]}" for c in CASES])
    def test_matches_pr3_driver(self, spinodal_state, name, target, pw,
                                mode):
        sim = BinaryFluidSim(GRID, params=PARAMS, target=target, fused=mode)
        out = sim.step(spinodal_state, 10)
        rf, rg = _pr3_trajectory(spinodal_state, 10, target, pw, mode)
        tdp.check_fields({"f": rf, "g": rg}, {"f": out.f, "g": out.g})

    @pytest.mark.parametrize("mode", [False, "one_launch", "two_launch"])
    def test_loop_matches_scan(self, spinodal_state, mode):
        sim = BinaryFluidSim(GRID, params=PARAMS, fused=mode)
        a = sim.step(spinodal_state, 10)
        b = sim.run(spinodal_state, 10)
        np.testing.assert_array_equal(np.asarray(a.f), np.asarray(b.f))
        np.testing.assert_array_equal(np.asarray(a.g), np.asarray(b.g))

    @pytest.mark.parametrize("nsteps", [4, 5])
    def test_pallas_scan_takes_two_steps_a_trip(self, spinodal_state,
                                                nsteps):
        """Under a Pallas executor ``run`` scans two steps a trip, so a
        kernel that cannot write over the state it reads costs no copy of
        the state per step; an odd count ends with one step alone.
        Streaming moves data only, so the scan is exact against single
        steps."""
        exe = lbp.stream_program().compile("pallas_windowed_interpret",
                                           grid_shape=GRID)
        state = {"f": spinodal_state.f, "g": spinodal_state.g}
        jaxpr = jax.make_jaxpr(exe._run_fn(nsteps, False))(
            exe._as_tuple(state))
        assert [e.params["unroll"] for e in _eqns(jaxpr.jaxpr)
                if e.primitive.name == "scan"] == [2]
        want = state
        for _ in range(nsteps):
            want = exe.step(want)
        got = exe.run(state, nsteps)
        for f in ("f", "g"):
            np.testing.assert_array_equal(np.asarray(got[f]),
                                          np.asarray(want[f]))

    def test_run_donated_matches_undonated(self, spinodal_state):
        sim = BinaryFluidSim(GRID, params=PARAMS, fused="two_launch")
        a = sim.run(spinodal_state, 6)
        st = BinaryFluidSim(GRID, params=PARAMS).init_spinodal(seed=3,
                                                               noise=0.05)
        with warnings.catch_warnings():
            # donation is a no-op on the CPU backend (XLA warns)
            warnings.filterwarnings("ignore",
                                    message="Some donated buffers")
            b = sim.run(st, 6, donate=True)
        np.testing.assert_array_equal(np.asarray(a.f), np.asarray(b.f))
        np.testing.assert_array_equal(np.asarray(a.g), np.asarray(b.g))


class TestExecute:
    """Program.execute — eager stepping with caller-managed ghosts (the
    surface ops.lb_fused_step runs on)."""

    def test_ghost_mode_matches_periodic(self, rng):
        consts = lbp.collision_consts(**PARAMS.as_kwargs())
        prog = lbp.fused_program("one_launch", consts)
        shape = (8, 8, 8)
        f = jnp.asarray(0.05 * rng.normal(size=(NVEL,) + shape) + 1 / 19.,
                        jnp.float32)
        g = jnp.asarray(0.05 * rng.normal(size=(NVEL,) + shape),
                        jnp.float32)
        ref = prog.execute("xla", {"f": f, "g": g}, grid_shape=shape)
        fe = jnp.concatenate([f[:, -2:], f, f[:, :2]], axis=1)
        ge = jnp.concatenate([g[:, -2:], g, g[:, :2]], axis=1)
        got = prog.execute("xla", {"f": fe, "g": ge}, grid_shape=shape,
                           halo=(2, 0, 0))
        tdp.check_fields(ref, got)

    def test_insufficient_ghosts_fail_fast(self, rng):
        consts = lbp.collision_consts(**PARAMS.as_kwargs())
        prog = lbp.fused_program("two_launch", consts)
        shape = (8, 8, 8)
        g1 = jnp.zeros((NVEL, 10, 8, 8), jnp.float32)
        with pytest.raises(ValueError, match="ghost layer"):
            prog.execute("xla", {"f": g1, "g": g1}, grid_shape=shape,
                         halo=(1, 0, 0))

    def test_missing_field(self):
        consts = lbp.collision_consts(**PARAMS.as_kwargs())
        prog = lbp.fused_program("one_launch", consts)
        with pytest.raises(ValueError, match="missing field 'g'"):
            prog.execute("xla", {"f": jnp.zeros((NVEL, 4, 4, 4))},
                         grid_shape=(4, 4, 4))


class TestCompiledProgram:
    def test_stage_target_routing_stencil_only(self):
        """Pointwise stages route to xla under a stencil-only target;
        stencil stages keep it (generalises the old sim fallback)."""
        consts = lbp.collision_consts(**PARAMS.as_kwargs())
        exe = lbp.collide_program(consts).compile(WINDOWED,
                                                  grid_shape=GRID)
        by_name = {st.name: t for st, t in zip(exe.program.stages,
                                               exe.stage_targets)}
        assert by_name["moments"].executor == "xla"
        assert by_name["collide"].executor == "xla"
        assert by_name["gradients"].executor == "pallas_windowed"

    def test_stage_target_keeps_pointwise_capable_executor(self):
        consts = lbp.collision_consts(**PARAMS.as_kwargs())
        exe = lbp.collide_program(consts).compile(
            tdp.Target("pallas_interpret", vvl=64), grid_shape=GRID)
        assert all(t.executor == "pallas_interpret"
                   for t in exe.stage_targets)

    def test_passthrough_field(self, rng):
        prog = tdp.program("p", [tdp.stage(double2, reads="a", writes="a")],
                           fields=("a", "b"))
        exe = prog.compile("xla", grid_shape=(4, 4))
        a = jnp.asarray(rng.normal(size=(2, 4, 4)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(2, 4, 4)), jnp.float32)
        out = exe.step({"a": a, "b": b})
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      2.0 * np.asarray(a))
        np.testing.assert_array_equal(np.asarray(out["b"]), np.asarray(b))

    def test_state_validation(self):
        prog = tdp.program("p", [tdp.stage(double2, reads="a", writes="a")],
                           fields=("a",))
        exe = prog.compile("xla", grid_shape=(4, 4))
        with pytest.raises(ValueError, match="missing field"):
            exe.step({})
        with pytest.raises(ValueError, match="field 'a'"):
            exe.step({"a": jnp.zeros((3, 4, 4))})      # wrong ncomp
        with pytest.raises(ValueError, match="field 'a'"):
            exe.step({"a": jnp.zeros((2, 5, 4))})      # wrong grid

    def test_run_zero_steps_is_identity(self):
        prog = tdp.program("p", [tdp.stage(double2, reads="a", writes="a")],
                           fields=("a",))
        exe = prog.compile("xla", grid_shape=(4, 4))
        a = jnp.ones((2, 4, 4))
        out = exe.run({"a": a}, 0)
        assert out["a"] is a

    def test_sharded_compile_validates_grid_vs_width(self):
        """Slabs thinner than the exchange width are fine (multi-hop
        ppermute), but a *global* X extent the schedule's width cannot
        fit in is a construction error."""
        consts = lbp.collision_consts(**PARAMS.as_kwargs())

        class FakeMesh:
            shape = {"data": 2}
        with pytest.raises(ValueError, match="ghost exchange"):
            lbp.fused_program("one_launch", consts).compile(
                "xla", grid_shape=(2, 8, 8), mesh=FakeMesh(),
                shard_axis="data")
        # slab (1 plane) < width (2) is NOT an error: the exchange hops
        # ranks (multi-hop ppermute) — trajectory pinned by the 4-way
        # slab=1 subprocess test in test_distributed.py
        w, _ = lbp.fused_program("one_launch", consts).schedule(
            3, (True, False, False))
        assert w == {"f": (2, 0, 0), "g": (2, 0, 0)}


def _fake_exchange(shards, dim, width):
    """Run :func:`exchange_ghosts` over stacked shards on one device.

    ``shards`` is ``(nranks, ncomp, *local)``; the injected permute
    reindexes the leading rank axis the way ``ppermute``'s
    ``(src, dst)`` pairs would route buffers, so the hop plan is
    exercised exactly as compiled — minus the mesh."""
    import importlib
    P = importlib.import_module("repro.core.program")
    n = shards.shape[0]

    def permute(x, pairs):
        idx = np.zeros(n, int)
        for src, dst in pairs:
            idx[dst] = src
        return x[jnp.asarray(idx)]

    # dim d of the *shard* is axis d+2 of the stack; exchange_ghosts
    # slices axis dim+1, so shift dim by one to skip the rank axis.
    return P.exchange_ghosts(shards, dim + 1, width, n, permute)


class TestPencilExchange:
    """The generalized (any-dim, any-hop-count) exchange round and the
    overlap partition — single-device unit pins; the end-to-end pencil /
    block / thin-pencil trajectories live in test_distributed.py."""

    def _prog_module(self):
        import importlib
        return importlib.import_module("repro.core.program")

    def test_exchange_hop_plan(self):
        P = self._prog_module()
        assert P._exchange_hops(2, 8) == [(1, 2)]       # neighbour covers
        assert P._exchange_hops(8, 8) == [(1, 8)]       # exactly one shard
        assert P._exchange_hops(3, 2) == [(1, 2), (2, 1)]
        assert P._exchange_hops(5, 2) == [(1, 2), (2, 2), (3, 1)]
        assert P._exchange_hops(2, 1) == [(1, 1), (2, 1)]
        assert sum(t for _, t in P._exchange_hops(5, 2)) == 5

    @pytest.mark.parametrize("nranks,loc,width", [
        (2, 4, 1), (2, 4, 3), (4, 2, 2),
        (4, 1, 2),            # thin pencil: 2 hops
        (3, 2, 5),            # width > 2 shards: 3 hops
        (8, 1, 4),            # maximal decomposition
    ])
    def test_exchange_matches_global_reference(self, nranks, loc, width,
                                               rng):
        """Enumerated fallback for the hypothesis property: the exchanged
        shard equals the wrap-indexed global array for every hop count —
        ghost planes concatenate in global-coordinate order."""
        glob = nranks * loc
        g = rng.normal(size=(2, glob)).astype(np.float32)
        shards = jnp.asarray(
            np.stack([g[:, i * loc:(i + 1) * loc] for i in range(nranks)]))
        got = np.asarray(_fake_exchange(shards, 0, width))
        assert got.shape == (nranks, 2, loc + 2 * width)
        for i in range(nranks):
            want = g[:, np.arange(i * loc - width,
                                  (i + 1) * loc + width) % glob]
            np.testing.assert_array_equal(got[i], want)

    def test_exchange_2d_shard_any_dim(self, rng):
        """Same reference check when the exchanged dim is not dim 0."""
        nranks, loc = 4, 2
        g = rng.normal(size=(1, 3, nranks * loc)).astype(np.float32)
        shards = jnp.asarray(np.stack(
            [g[:, :, i * loc:(i + 1) * loc] for i in range(nranks)]))
        got = np.asarray(_fake_exchange(shards, 1, 3))   # 2 hops
        for i in range(nranks):
            want = g[:, :, np.arange(i * loc - 3,
                                     (i + 1) * loc + 3) % (nranks * loc)]
            np.testing.assert_array_equal(got[i], want)

    @pytest.mark.parametrize("local,W,shard_dims", [
        ((8, 8, 16), (1, 1, 0), (0, 1)),
        ((8, 8, 16), (2, 2, 0), (0, 1)),
        ((4, 4, 4), (1, 1, 1), (0, 1, 2)),
        ((8, 4, 8), (2, 0, 0), (0, 1)),      # dim 1 unexchanged
        ((6, 8), (2, 1), (0, 1)),
    ])
    def test_overlap_regions_tile_exactly_once(self, local, W, shard_dims):
        """Interior + boundary slabs partition the local domain: every
        site covered exactly once (corners belong to the lowest exchanged
        dim's slabs)."""
        P = self._prog_module()
        (i_start, i_shape), bounds = P._overlap_regions(local, W,
                                                        shard_dims)
        cover = np.zeros(local, np.int32)

        def mark(start, shape):
            cover[tuple(slice(s, s + n) for s, n in zip(start, shape))] += 1

        mark(i_start, i_shape)
        for d, lo, hi in bounds:
            mark(*lo)
            mark(*hi)
        assert (cover == 1).all()
        # interior sits W away from every exchanged face
        for d in shard_dims:
            if W[d]:
                assert i_start[d] == W[d]
                assert i_shape[d] == local[d] - 2 * W[d]

    def test_exchange_stats_arithmetic(self):
        P = self._prog_module()
        cs = P.exchange_stats({"f": (1, 1, 0), "g": (2, 2, 0)},
                              {"f": 19, "g": 19}, (8, 8, 16), (0, 1))
        f = cs["per_field"]["f"]
        # dim 0: 2*1*(8*16) planes; dim 1 spans the dim-0-extended
        # extent: 2*1*(10*16)
        assert f["bytes"] == (2 * 8 * 16 + 2 * 10 * 16) * 19 * 4
        assert f["ppermutes"] == 4
        g = cs["per_field"]["g"]
        assert g["bytes"] == (2 * 2 * 8 * 16 + 2 * 2 * 12 * 16) * 19 * 4
        assert cs["exchanged_bytes_per_step"] == f["bytes"] + g["bytes"]
        assert cs["ppermutes_per_step"] == 8
        # a thin dim multiplies ppermutes (multi-hop), not bytes
        th = P.exchange_stats({"g": (2,)}, {"g": 1}, (1,), (0,))
        assert th["per_field"]["g"]["ppermutes"] == 2 * 2
        assert th["per_field"]["g"]["bytes"] == 2 * 2 * 1 * 4

    # -- compile-time validation (the bugfix sweep) ------------------------

    def consts(self):
        return lbp.collision_consts(**PARAMS.as_kwargs())

    class _Mesh2x2:
        shape = {"px": 2, "py": 2}

    def test_pencil_divisibility_error_names_dim_and_axis(self):
        with pytest.raises(ValueError, match=r"Y extent 9 not divisible "
                                             r"by mesh axis py=2"):
            lbp.fused_program("one_launch", self.consts()).compile(
                "xla", grid_shape=(8, 9, 8), mesh=self._Mesh2x2(),
                shard_axis=("px", "py"))

    def test_pencil_unknown_and_duplicate_axes(self):
        prog = lbp.fused_program("one_launch", self.consts())
        with pytest.raises(ValueError, match="not a mesh axis"):
            prog.compile("xla", grid_shape=(8, 8, 8), mesh=self._Mesh2x2(),
                         shard_axis=("px", "pz"))
        with pytest.raises(ValueError, match="duplicate shard axes"):
            prog.compile("xla", grid_shape=(8, 8, 8), mesh=self._Mesh2x2(),
                         shard_axis=("px", "px"))
        with pytest.raises(ValueError, match="at most 2"):
            prog.compile("xla", grid_shape=(8, 8), mesh=self._Mesh2x2(),
                         shard_axis=("px", "py", "px2"))

    def test_pencil_width_vs_global_extent_any_dim(self):
        """The slab-era width check now runs per sharded dim: a dim-1
        global extent the schedule cannot fit fails at compile."""
        with pytest.raises(ValueError, match="ghost exchange in dim 1"):
            lbp.fused_program("one_launch", self.consts()).compile(
                "xla", grid_shape=(8, 2, 8), mesh=self._Mesh2x2(),
                shard_axis=("px", "py"))

    def test_closed_dim_thinner_than_radius_fails_at_compile(self):
        """An *unsharded* stencil-read dim wraps periodically inside each
        launch — a radius-2 schedule meeting an extent-1 closed dim must
        fail at compile with the decomposition named, not deep inside
        lax.scan."""
        prog = lbp.fused_program("one_launch", self.consts())

        class Slab:
            shape = {"data": 2}
        with pytest.raises(ValueError,
                           match=r"unsharded \(periodic\) extent 1"):
            prog.compile("xla", grid_shape=(8, 8, 1), mesh=Slab(),
                         shard_axis="data")
        # unsharded compiles hit the same guard
        with pytest.raises(ValueError, match="shard dim 2 with a mesh"):
            prog.compile("xla", grid_shape=(8, 8, 1))

    def test_halo_extend_wrap_thinner_than_radius(self):
        """Satellite pin for the halo_extend bugfix: the periodic path
        refuses a wrap wider than one period, naming dim/radius/extent."""
        from repro.core import halo_extend
        from repro.lb.stencil import FUSED_SPEC
        stc = max((s for s in FUSED_SPEC.stencils if s is not None),
                  key=lambda s: max(s.radius_per_dim()))
        assert max(stc.radius_per_dim()) == 2
        x = jnp.ones((1, 8 * 8 * 1), jnp.float32)
        with pytest.raises(ValueError, match="radius 2 in dim 2 exceeds "
                                             "the periodic extent 1"):
            halo_extend(x, (8, 8, 1), (0, 0, 0), stc)
        # and the launch-level guard fires before tracing
        with pytest.raises(ValueError, match="cannot wrap-pad"):
            tdp.launch_plan(lbst.FUSED_SPEC, WINDOWED,
                            lattice=Lattice((8, 8, 1)))


class TestProgramPlan:
    """Program.plan aggregates the PR 3 memory models across stages."""

    def consts(self):
        return lbp.collision_consts(**PARAMS.as_kwargs())

    def test_sum_and_max_aggregation(self):
        from repro.core import launch_plan
        prog = lbp.fused_program("two_launch", self.consts())
        plan = prog.plan(tdp.Target("xla", vvl=128), grid_shape=GRID)
        lat = Lattice(GRID)
        a = launch_plan(lbst.PHI_STREAM_SPEC, tdp.Target("xla", vvl=128),
                        lattice=lat)
        b = launch_plan(lbst.FUSED_TWO_SPEC, tdp.Target("xla", vvl=128),
                        lattice=lat, consts=self.consts())
        assert plan.hbm_bytes_estimate() == (a.hbm_bytes_estimate()
                                             + b.hbm_bytes_estimate())
        assert plan.vmem_bytes_estimate() == max(a.vmem_bytes_estimate(),
                                                 b.vmem_bytes_estimate())
        assert [r["stage"] for r in plan.per_stage()] == ["phi_stream",
                                                          "fused_two"]

    def test_windowed_plan_is_gather_free(self):
        """The acceptance pin: the fused step's aggregated per-step HBM
        footprint under the windowed target carries no noffsets× term."""
        prog = lbp.fused_program("one_launch", self.consts())
        g = prog.plan(tdp.Target("xla"), grid_shape=(64, 64, 64))
        w = prog.plan(WINDOWED, grid_shape=(64, 64, 64))
        assert g.hbm_bytes_estimate() > 1.3 * 2 ** 30
        assert w.hbm_bytes_estimate() < 100 * 2 ** 20
        assert all(r["wants"] == "halo_extended" for r in w.per_stage())

    @pytest.mark.parametrize("decomposition, wrapped", [
        ("one_chip", (0, 1, 2)), ("pencil", (2,))])
    def test_per_stage_reports_in_kernel_wrap(self, decomposition,
                                              wrapped):
        """The counter that the in-kernel wrap engaged: every stencil
        field of both fused windowed launches wraps all three dims on
        one chip, and only the unsharded z on a 2×2 pencil (x and y
        carry exchanged ghosts)."""
        from repro.core.program import (_build_program_plan,
                                        resolve_stage_target)
        prog = lbp.fused_program("two_launch", self.consts())
        open_dims = (decomposition == "pencil",) * 2 + (False,)
        widths, geo = prog.schedule(3, open_dims)
        targets = tuple(resolve_stage_target(WINDOWED, st.spec, st.name)
                        for st in prog.stages)
        plan = _build_program_plan(prog, targets, GRID, geo, widths)
        rows = plan.per_stage()
        assert [r["stage"] for r in rows] == ["phi_stream", "fused_two"]
        assert [r["wrap_dims"] for r in rows] == [(wrapped,),
                                                  (wrapped,) * 3]

    def test_plan_routes_pointwise_stages(self):
        plan = lbp.collide_program(self.consts()).plan(WINDOWED,
                                                       grid_shape=GRID)
        ex = {r["stage"]: r["executor"] for r in plan.per_stage()}
        assert ex["moments"] == "xla" and ex["collide"] == "xla"
        assert ex["gradients"] == "pallas_windowed"

    def test_compiled_plan_reports_halo_schedule(self):
        sim = BinaryFluidSim((16, 8, 8), params=PARAMS, fused="two_launch")
        assert sim.programs["fused"].halo_schedule == {}    # unsharded
        consts = lbp.collision_consts(**PARAMS.as_kwargs())
        w, _ = lbp.fused_program("two_launch", consts).schedule(3, OPEN_X)
        assert {k: v[0] for k, v in w.items()} == {"f": 1, "g": 2}


class TestShimWarningsOncePerCallSite:
    """core/execute.py's deprecation shims use the standard warnings
    machinery: with the default filter each *call site* warns exactly
    once, however many times it executes."""

    def _collect(self, fn, warmup):
        with warnings.catch_warnings():
            # jit compilation inside the first call mutates the global
            # warning filters (invalidating the per-call-site registry);
            # warm the launch cache first so the measurement below sees
            # stable filter state.
            warnings.simplefilter("ignore")
            warmup()
        with warnings.catch_warnings(record=True) as rec:
            warnings.resetwarnings()
            warnings.simplefilter("default")
            fn()
        return [w for w in rec if issubclass(w.category,
                                             DeprecationWarning)]

    def test_launch_once_per_call_site(self):
        from repro.core.execute import launch as legacy_launch
        x = jnp.ones((2, 8), jnp.float32)

        def warmup():
            legacy_launch(double2.fn, None, [x], out_ncomp=2)

        def body():
            for _ in range(3):
                legacy_launch(double2.fn, None, [x], out_ncomp=2)

        assert len(self._collect(body, warmup)) == 1

        def two_sites():
            legacy_launch(double2.fn, None, [x], out_ncomp=2)
            legacy_launch(double2.fn, None, [x], out_ncomp=2)

        assert len(self._collect(two_sites, warmup)) == 2

    def test_launch_stencil_once_per_call_site(self):
        from repro.core.execute import launch_stencil as legacy_stencil
        lat = Lattice((4, 4, 4))
        phi = jnp.ones((1, lat.nsites), jnp.float32)

        def warmup():
            legacy_stencil(star_sum.fn, lat, [phi],
                           stencil=STENCIL_GRAD_6PT, out_ncomp=1)

        def body():
            for _ in range(3):
                legacy_stencil(star_sum.fn, lat, [phi],
                               stencil=STENCIL_GRAD_6PT, out_ncomp=1)

        assert len(self._collect(body, warmup)) == 1
