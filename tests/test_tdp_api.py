"""The declarative targetDP API: KernelSpec + Target + executor registry.

Pins the redesign's contract (docs/targetdp_api.md):

* one ``tdp.launch(spec, target, *arrays, **consts)`` entry point for
  pointwise and stencil kernels;
* ``Target`` replaces the stringly backend/vvl plumbing and participates
  in the plan cache key (the ``set_default_vvl`` staleness regression);
* the executor table is open — a mock executor registered via
  ``register_executor`` runs end-to-end pointwise *and* stencil launches
  without touching core;
* the deprecated ``launch``/``launch_stencil`` shims warn and produce
  bit-identical outputs.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tdp
from repro.core import launch as legacy_launch
from repro.core import launch_stencil as legacy_launch_stencil
from repro.core import Lattice, STENCIL_GRAD_6PT, TargetConst


@tdp.kernel(fields=[tdp.field(2)], out=2, consts=["a"])
def scale2(x, a=1.0):
    return a * x


GRAD_SPEC = tdp.KernelSpec(
    lambda p: (p[1] - p[2], p[0, 0][None]),
    fields=(tdp.field(1, stencil=STENCIL_GRAD_6PT),),
    out=(1, 1), name="grad_pair")


class TestTarget:
    def test_coercion(self):
        assert tdp.as_target(None) == tdp.Target("xla")
        assert tdp.as_target("pallas").backend == "pallas"
        t = tdp.as_target("xla", vvl=64)
        assert t.vvl == 64
        with pytest.raises(TypeError):
            tdp.as_target(123)

    def test_pallas_interpret_canonicalises(self):
        t = tdp.Target("pallas_interpret")
        assert t.backend == "pallas" and t.interpret
        assert t.executor == "pallas_interpret"
        assert t == tdp.Target("pallas", interpret=True)

    def test_tuning_is_hashable_and_ordered(self):
        a = tdp.Target("pallas", tuning={"block_f": 256, "block_q": 64})
        b = tdp.Target("pallas", tuning={"block_q": 64, "block_f": 256})
        assert a == b and hash(a) == hash(b)
        assert a.tune("block_f") == 256
        assert a.tune("missing", 7) == 7

    def test_validation(self):
        with pytest.raises(ValueError):
            tdp.Target("xla", vvl=0)
        with pytest.raises(ValueError):
            tdp.Target("")

    def test_with_updates(self):
        t = tdp.Target("xla").with_(vvl=32)
        assert t.vvl == 32 and t.backend == "xla"


class TestKernelSpec:
    def test_decorator_builds_spec(self):
        assert isinstance(scale2, tdp.KernelSpec)
        assert scale2.name == "scale2"
        assert scale2.out == (2,)
        assert scale2.fields[0].role == "pointwise"
        # the spec stays callable as its body
        np.testing.assert_allclose(
            scale2(jnp.ones((2, 4)), a=3.0), 3.0 * np.ones((2, 4)))

    def test_field_coercions(self):
        spec = tdp.KernelSpec(lambda x, y: x, fields=(STENCIL_GRAD_6PT, 3),
                              out=1)
        assert spec.fields[0].stencil is STENCIL_GRAD_6PT
        assert spec.fields[1].ncomp == 3 and spec.fields[1].stencil is None

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            tdp.KernelSpec(lambda x: x, fields=())
        with pytest.raises(TypeError):
            tdp.KernelSpec("not callable", fields=(tdp.field(),))
        with pytest.raises(ValueError):
            tdp.field(stencil=None, halo="ghost")
        with pytest.raises(ValueError):
            tdp.field(halo="sometimes")


class TestLaunchErrors:
    """The error paths the redesign is contractually required to catch."""

    def test_non_spec_first_argument(self):
        with pytest.raises(TypeError, match="KernelSpec"):
            tdp.launch(lambda x: x, None, jnp.zeros((1, 8)))

    def test_role_vs_rank_mismatch(self):
        with pytest.raises(ValueError, match="rank"):
            tdp.launch(scale2, None, jnp.zeros((8,)))
        with pytest.raises(ValueError, match="rank"):
            tdp.launch(scale2, None, jnp.zeros((1, 2, 8)))

    def test_declared_ncomp_mismatch(self):
        with pytest.raises(ValueError, match="ncomp"):
            tdp.launch(scale2, None, jnp.zeros((3, 8)))

    def test_field_count_mismatch(self):
        with pytest.raises(ValueError, match="field"):
            tdp.launch(scale2, None, jnp.zeros((2, 8)), jnp.zeros((2, 8)))

    def test_unknown_executor_name(self):
        with pytest.raises(ValueError, match="unknown executor"):
            tdp.launch(scale2, "cuda", jnp.zeros((2, 8)))
        with pytest.raises(ValueError, match="unknown executor"):
            tdp.get_executor("definitely_not_registered")

    def test_stencil_missing_lattice(self):
        x = jnp.zeros((1, 64), jnp.float32)
        with pytest.raises(ValueError, match="missing a lattice"):
            tdp.launch(GRAD_SPEC, None, x)

    def test_undeclared_const_rejected(self):
        with pytest.raises(ValueError, match="const"):
            tdp.launch(scale2, None, jnp.ones((2, 8)), b=2.0)

    def test_halo_policy_enforced(self):
        spec = tdp.KernelSpec(lambda p: p[0], fields=(
            tdp.field(1, stencil=STENCIL_GRAD_6PT, halo="ghost"),), out=1)
        lat = Lattice((4, 4, 4))
        with pytest.raises(ValueError, match="ghost"):
            tdp.launch(spec, None, jnp.zeros((1, 64), jnp.float32),
                       lattice=lat)

    def test_duplicate_executor_registration(self):
        tdp.register_executor("dup_exec", lambda plan, g: g)
        try:
            with pytest.raises(ValueError, match="already registered"):
                tdp.register_executor("dup_exec", lambda plan, g: g)
            # overwrite=True is the sanctioned replacement path
            tdp.register_executor("dup_exec", lambda plan, g: g,
                                  overwrite=True)
        finally:
            tdp.unregister_executor("dup_exec")
        with pytest.raises(ValueError):
            tdp.unregister_executor("dup_exec")


class TestMockExecutor:
    """register_executor alone suffices for end-to-end pointwise AND
    stencil launches — no core/execute.py (or core/api.py) edits."""

    @staticmethod
    def _whole_lattice_executor(plan, gathered):
        # One "chunk" spanning the whole lattice: site kernels are shape-
        # polymorphic in V, so the body runs unchanged with V = nsites.
        args = list(gathered)
        if plan.with_site_index:
            args.append(jnp.arange(gathered[0].shape[-1], dtype=jnp.int32))
        vals = plan.kernel(*args, **plan.consts)
        return vals if isinstance(vals, tuple) else (vals,)

    def test_pointwise_and_stencil_end_to_end(self, rng):
        tdp.register_executor("mock", self._whole_lattice_executor)
        try:
            x = jnp.asarray(rng.normal(size=(2, 42)), jnp.float32)
            got = tdp.launch(scale2, tdp.Target("mock"), x, a=2.0)
            want = tdp.launch(scale2, tdp.Target("xla", vvl=16), x, a=2.0)
            tdp.check_fields([want], [got])

            lat = Lattice((4, 4, 4))
            phi = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
            ga, gb = tdp.launch(GRAD_SPEC, tdp.Target("mock"), phi,
                                lattice=lat)
            wa, wb = tdp.launch(GRAD_SPEC, tdp.Target("xla", vvl=16), phi,
                                lattice=lat)
            tdp.check_fields([wa, wb], [ga, gb])
        finally:
            tdp.unregister_executor("mock")

    def test_custom_executor_drives_fused_lb_op(self, rng):
        """ops.lb_fused_step dispatches through the registry — a custom
        executor runs the full fused LB step with no ops/core edits."""
        from repro.kernels import ops
        from repro.kernels.lb_collision import NVEL
        tdp.register_executor("mock_lb", self._whole_lattice_executor)
        try:
            shape = (4, 4, 4)
            n = 64
            f = jnp.asarray(0.05 * rng.normal(size=(NVEL, n)) + 1 / 19.,
                            jnp.float32)
            g = jnp.asarray(0.05 * rng.normal(size=(NVEL, n)), jnp.float32)
            got = ops.lb_fused_step(f, g, grid_shape=shape,
                                    target=tdp.Target("mock_lb"))
            want = ops.lb_fused_step(f, g, grid_shape=shape, backend="xla",
                                     vvl=32)
            tdp.check_fields(dict(zip("fg", want)), dict(zip("fg", got)))
        finally:
            tdp.unregister_executor("mock_lb")

    def test_reregistration_invalidates_cached_plans(self, rng):
        x = jnp.asarray(rng.normal(size=(2, 16)), jnp.float32)
        tdp.register_executor("flip", lambda plan, g: (g[0],))
        try:
            first = tdp.launch(scale2, tdp.Target("flip"), x, a=2.0)
            np.testing.assert_allclose(np.asarray(first), np.asarray(x))
            tdp.register_executor("flip", lambda plan, g: (-g[0],),
                                  overwrite=True)
            second = tdp.launch(scale2, tdp.Target("flip"), x, a=2.0)
            np.testing.assert_allclose(np.asarray(second), -np.asarray(x))
        finally:
            tdp.unregister_executor("flip")


@tdp.kernel(fields=[tdp.field(1)], out=1)
def chunk_width(x):
    """Reports the VVL the compiled closure was built with — padding lanes
    included, so any stale closure is immediately visible."""
    return jnp.full_like(x, x.shape[-1])


class TestVVLStaleness:
    """Regression: two launches of one kernel under different *default*
    VVLs must not reuse one closure (the old global-mutation bug class)."""

    def test_set_default_vvl_rebuilds_closure(self):
        x = jnp.zeros((1, 256), jnp.float32)
        old = tdp.default_vvl()
        try:
            tdp.set_default_vvl(32)
            a = tdp.launch(chunk_width, None, x)   # Target(vvl=None)
            assert float(a[0, 0]) == 32.0
            tdp.set_default_vvl(64)
            b = tdp.launch(chunk_width, None, x)
            assert float(b[0, 0]) == 64.0, "stale closure reused"
        finally:
            tdp.set_default_vvl(old)

    def test_explicit_vvl_wins_over_default(self):
        x = jnp.zeros((1, 256), jnp.float32)
        old = tdp.default_vvl()
        try:
            tdp.set_default_vvl(32)
            a = tdp.launch(chunk_width, tdp.Target("xla", vvl=128), x)
            assert float(a[0, 0]) == 128.0
        finally:
            tdp.set_default_vvl(old)

    def test_legacy_shim_also_tracks_default(self):
        x = jnp.zeros((1, 256), jnp.float32)
        old = tdp.default_vvl()
        try:
            tdp.set_default_vvl(32)
            with pytest.warns(DeprecationWarning):
                a = legacy_launch(chunk_width.fn, None, [x])
            tdp.set_default_vvl(64)
            with pytest.warns(DeprecationWarning):
                b = legacy_launch(chunk_width.fn, None, [x])
            assert float(a[0, 0]) == 32.0 and float(b[0, 0]) == 64.0
        finally:
            tdp.set_default_vvl(old)


class TestShimEquivalence:
    """launch / launch_stencil warn, then delegate — bit-identical."""

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_pointwise_bit_identical(self, backend, rng):
        lat = Lattice((6, 7))
        x = jnp.asarray(rng.normal(size=(2, lat.nsites)), jnp.float32)
        a = TargetConst(np.float32(1.5))
        new = tdp.launch(scale2, tdp.Target(backend, vvl=16), x,
                         lattice=lat, a=a)
        with pytest.warns(DeprecationWarning):
            old = legacy_launch(scale2.fn, lat, [x], consts={"a": a},
                                vvl=16, backend=backend)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_stencil_bit_identical(self, backend, rng):
        from repro.lb import stencil as lbst
        lat = Lattice((3, 4, 5))
        phi = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
        gn, ln = tdp.launch(lbst.GRAD6_SPEC, tdp.Target(backend, vvl=32),
                            phi, lattice=lat)
        with pytest.warns(DeprecationWarning):
            go, lo = legacy_launch_stencil(
                lbst.grad6_site_kernel, lat, [phi],
                stencil=STENCIL_GRAD_6PT, out_ncomp=(3, 1), vvl=32,
                backend=backend)
        np.testing.assert_array_equal(np.asarray(gn), np.asarray(go))
        np.testing.assert_array_equal(np.asarray(ln), np.asarray(lo))

    def test_shims_are_thin(self):
        import inspect
        from repro.core import execute

        for fn in (execute.launch, execute.launch_stencil):
            src = inspect.getsource(fn)
            body = src.split('stacklevel=2)', 1)[1]
            stmts = [l for l in body.splitlines()
                     if l.strip() and not l.strip().startswith("#")]
            assert len(stmts) <= 15, f"{fn.__name__} is not a thin shim"


class TestOpsTargets:
    """kernels/ops.py accepts Target objects; strings only coerce through
    as_target (via op_target)."""

    def test_target_and_backend_are_exclusive(self):
        from repro.kernels import ops
        with pytest.raises(ValueError, match="not both"):
            ops.op_target(tdp.Target("xla"), "xla", None)

    def test_op_accepts_target_and_string(self, rng):
        from repro.kernels import ops
        x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
        a = ops.rmsnorm(x, w, target=tdp.Target("pallas_interpret", vvl=64))
        b = ops.rmsnorm(x, w, backend="pallas_interpret", vvl=64)
        c = ops.rmsnorm(x, w, target="pallas_interpret", vvl=64)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def test_tuning_dict_feeds_block_sizes(self, rng):
        from repro.kernels import ops
        u = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
        t = tdp.Target("pallas_interpret", vvl=32,
                       tuning={"block_f": 32})
        got = ops.gated_act(u, v, target=t)
        want = ops.gated_act(u, v, backend="xla")
        tdp.check_fields([want], [got])
