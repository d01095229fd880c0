"""The first-class AoSoA/VVL layout axis (ISSUE 10).

Pinned here:

* the SoA↔AoSoA transforms are exact inverses for every extent —
  odd sizes, remainder blocks, ``nsites < vvl``, ``ncomp > 1``, and
  leading (``noffsets``) axes — with zero-padded pad lanes;
* every executor (gathered xla / pallas, windowed pallas) produces the
  same outputs under ``layout="aosoa"`` as under SoA for every valid
  vvl (held to the cross-path contract, ``check_fields``), including
  mixed pointwise+stencil kernels, consts, site_index, multi-output,
  and ``plane_block > 1`` windows;
* the 10-step LB fused trajectory at 16³ agrees across layout × vvl ×
  executor;
* the ported LM kernels (rmsnorm / gated_act / mamba_scan) run through
  ``tdp.launch`` on both layouts with the same results — the
  beyond-the-lattice acceptance pin;
* plan-build validation: an indivisible windowed-AoSoA vvl and a
  VMEM-overflowing window each raise *named* compile-time errors;
  ``tdp.autotune`` prunes such candidates instead of crashing;
* the autotune space grows vvl / layout axes, candidate 0 wins ties,
  and cache entries round-trip the new fields.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import tdp
from repro.core import (
    FieldSpec,
    KernelSpec,
    Lattice,
    Stencil,
    Target,
    WindowVmemError,
    aosoa_to_soa,
    as_target,
    check_fields,
    soa_to_aosoa,
)
from repro.core.api import launch, launch_plan
from repro.core.layout import aosoa_nblocks, plane_from_aosoa, plane_to_aosoa


@pytest.fixture
def rng():
    return np.random.default_rng(7)


D3Q7 = Stencil("d3q7", ((0, 0, 0), (1, 0, 0), (-1, 0, 0), (0, 1, 0),
                        (0, -1, 0), (0, 0, 1), (0, 0, -1)))


# ---------------------------------------------------------------------------
# transforms
# ---------------------------------------------------------------------------

class TestTransforms:
    @pytest.mark.parametrize("shape", [(1, 7), (3, 100), (2, 128),
                                       (5, 3, 100), (19, 1, 31)])
    @pytest.mark.parametrize("vvl", [1, 4, 7, 128])
    def test_round_trip_exact(self, rng, shape, vvl):
        """Remainder sites, odd extents, nsites < vvl, leading axes —
        the enumerated fallback for the hypothesis sweep in
        test_properties.py."""
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        y = soa_to_aosoa(x, vvl)
        assert y.shape[0] == aosoa_nblocks(shape[-1], vvl)
        assert y.shape[-1] == vvl
        back = aosoa_to_soa(y, shape[-1])
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))

    def test_remainder_lanes_zero_padded(self, rng):
        x = jnp.asarray(rng.normal(size=(2, 5)).astype(np.float32))
        y = np.asarray(soa_to_aosoa(x, 4))          # 2 blocks, 3 pad lanes
        assert y.shape == (2, 2, 4)
        np.testing.assert_array_equal(y[1, :, 1:], 0.0)

    def test_aosoa_block_is_contiguous_tile(self, rng):
        """Block b holds components interleaved per block: y[b, c, l] ==
        x[c, b·vvl + l] — the paper's [site-block][component][lane]."""
        x = jnp.asarray(rng.normal(size=(3, 12)).astype(np.float32))
        y = np.asarray(soa_to_aosoa(x, 4))
        xn = np.asarray(x)
        for b in range(3):
            for c in range(3):
                np.testing.assert_array_equal(
                    y[b, c], xn[c, b * 4:(b + 1) * 4])

    def test_plane_round_trip_and_divisibility(self, rng):
        x = jnp.asarray(rng.normal(size=(3, 6, 4, 8)).astype(np.float32))
        y = plane_to_aosoa(x, 8)
        assert y.shape == (6, 4, 3, 8)               # (npl, nblk, ncomp, vvl)
        back = plane_from_aosoa(y, (4, 8))
        np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
        with pytest.raises(ValueError, match="not divisible"):
            plane_to_aosoa(x, 7)

    def test_layout_validated_on_target(self):
        with pytest.raises(ValueError, match="layout"):
            Target("xla", layout="aos")
        assert as_target("xla", layout="aosoa").layout == "aosoa"
        assert Target("xla").layout == "soa"


# ---------------------------------------------------------------------------
# executor bit-identity
# ---------------------------------------------------------------------------

def _mixed_spec():
    def body(f_nb, rho, idx, *, alpha, w):
        # stencil chunk (7, 2, V), pointwise chunk (1, V), site idx (V,)
        acc = (f_nb * w.reshape(-1, 1, 1)).sum(axis=0)     # (2, V)
        return (alpha * acc + rho + (idx % 3).astype(acc.dtype),
                acc[:1] - rho)

    return KernelSpec(
        body, fields=(FieldSpec(2, stencil=D3Q7, name="f"),
                      FieldSpec(1, name="rho")),
        out=(2, 1), site_index=True, consts=("alpha", "w"),
        name="mixed_layout")


class TestExecutorBitIdentity:
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    @pytest.mark.parametrize("vvl", [32, 60, 128])
    def test_gathered_layouts_identical(self, rng, backend, vvl):
        """Gathered executors: any vvl (remainder pads), mixed stencil +
        pointwise + consts + site_index, multi-output."""
        lat = Lattice((4, 6, 5))
        spec = _mixed_spec()
        f = jnp.asarray(rng.normal(size=(2, lat.nsites)).astype(np.float32))
        r = jnp.asarray(rng.normal(size=(1, lat.nsites)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(7,)).astype(np.float32))
        outs = {}
        for layout in ("soa", "aosoa"):
            t = Target(backend, vvl=vvl, layout=layout)
            outs[layout] = launch(spec, t, f, r, lattice=lat,
                                  consts={"alpha": 1.5, "w": w})
        check_fields(outs["soa"], outs["aosoa"])

    @pytest.mark.parametrize("vvl", [8, 16, 32])
    @pytest.mark.parametrize("plane_block", [1, 2, 4])
    def test_windowed_layouts_identical(self, rng, vvl, plane_block):
        """The windowed executor's AoSoA VMEM tiles reproduce the SoA
        path for every valid vvl × plane_block."""
        lat = Lattice((8, 8, 4))                 # interior plane = 32 sites
        spec = _mixed_spec()
        f = jnp.asarray(rng.normal(size=(2, lat.nsites)).astype(np.float32))
        r = jnp.asarray(rng.normal(size=(1, lat.nsites)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(7,)).astype(np.float32))
        outs = {}
        for layout in ("soa", "aosoa"):
            t = Target("pallas_windowed", vvl=vvl, layout=layout,
                       interpret=True, tuning={"plane_block": plane_block})
            outs[layout] = launch(spec, t, f, r, lattice=lat,
                                  consts={"alpha": 1.5, "w": w})
        check_fields(outs["soa"], outs["aosoa"])

    def test_windowed_matches_xla_under_aosoa(self, rng):
        lat = Lattice((6, 4, 8))
        spec = _mixed_spec()
        f = jnp.asarray(rng.normal(size=(2, lat.nsites)).astype(np.float32))
        r = jnp.asarray(rng.normal(size=(1, lat.nsites)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(7,)).astype(np.float32))
        a = launch(spec, Target("pallas_windowed", vvl=16, layout="aosoa",
                                interpret=True), f, r, lattice=lat,
                   consts={"alpha": 1.5, "w": w})
        b = launch(spec, Target("xla", vvl=64), f, r, lattice=lat,
                   consts={"alpha": 1.5, "w": w})
        check_fields(b, a)


@pytest.mark.slow
class TestLBTrajectory:
    """Acceptance pin: 10 fused LB steps at 16³ agree across layout ×
    vvl × executor under the cross-path contract."""

    def test_trajectory_layout_sweep(self):
        from repro.lb.params import LBParams
        from repro.lb.sim import BinaryFluidSim

        p = LBParams(A=0.125, B=0.125, kappa=0.02)
        base = BinaryFluidSim((16, 16, 16), params=p, fused="one_launch")
        st0 = base.init_spinodal(seed=3, noise=0.05)
        want = base.step(st0, 10)
        for backend, vvls in [("xla", (64, 128)),
                              ("pallas_windowed", (64, 256))]:
            for vvl in vvls:
                t = Target(backend, vvl=vvl, layout="aosoa",
                           interpret=backend != "xla")
                sim = BinaryFluidSim((16, 16, 16), params=p,
                                     fused="one_launch", target=t)
                got = sim.step(st0, 10)
                check_fields({"f": want.f, "g": want.g},
                             {"f": got.f, "g": got.g})


# ---------------------------------------------------------------------------
# the ported LM kernels (beyond the lattice)
# ---------------------------------------------------------------------------

class TestPortedKernels:
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_rmsnorm_layouts_identical(self, rng, backend):
        from repro.kernels import ops
        x = jnp.asarray(rng.normal(size=(100, 64)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        outs = [np.asarray(ops.rmsnorm(
            x, w, target=Target(backend, vvl=32, layout=lay)))
            for lay in ("soa", "aosoa")]
        check_fields([outs[0]], [outs[1]])
        from repro.kernels import ref
        np.testing.assert_allclose(outs[0], np.asarray(ref.rmsnorm_ref(x, w)),
                                   rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("kind", ["swiglu", "geglu", "relu2"])
    def test_gated_act_layouts_identical(self, rng, kind):
        from repro.kernels import ops
        u = jnp.asarray(rng.normal(size=(33, 48)).astype(np.float32))
        v = (None if kind == "relu2"
             else jnp.asarray(rng.normal(size=(33, 48)).astype(np.float32)))
        outs = [np.asarray(ops.gated_act(
            u, v, kind=kind,
            target=Target("pallas_interpret", vvl=96, layout=lay)))
            for lay in ("soa", "aosoa")]
        check_fields([outs[0]], [outs[1]])

    def test_mamba_scan_layouts_identical(self, rng):
        from repro.kernels import ops
        batch, L, d_inner, n = 2, 24, 48, 8
        x = jnp.asarray(rng.normal(size=(batch, L, d_inner)), jnp.float32)
        dt = jnp.asarray(0.1 * abs(rng.normal(size=(batch, L, d_inner))),
                         jnp.float32)
        b = jnp.asarray(rng.normal(size=(batch, L, n)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(batch, L, n)), jnp.float32)
        a = jnp.asarray(-abs(rng.normal(size=(d_inner, n))), jnp.float32)
        d = jnp.asarray(rng.normal(size=(d_inner,)), jnp.float32)
        got = {}
        for lay in ("soa", "aosoa"):
            t = Target("pallas_interpret", vvl=16, layout=lay)
            got[lay] = ops.mamba_scan(x, dt, b, c, a, d, target=t)
        check_fields(got["soa"], got["aosoa"])
        from repro.kernels import ref
        y_ref, h_ref = ref.mamba_scan_ref(x, dt, b, c, a, d)
        np.testing.assert_allclose(np.asarray(got["soa"][0]),
                                   np.asarray(y_ref), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(got["soa"][1]),
                                   np.asarray(h_ref), rtol=2e-4, atol=2e-4)

    def test_rmsnorm_weight_gradient_flows(self, rng):
        """The weight rides as a dynamic const — jax.grad must see it."""
        import jax
        from repro.kernels import ops
        x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))

        def loss(w_, backend):
            return (ops.rmsnorm(x, w_, target=Target(backend)) ** 2).sum()

        g_xla = jax.grad(lambda w_: loss(w_, "xla"))(w)
        assert float(jnp.abs(g_xla).max()) > 0


# ---------------------------------------------------------------------------
# plan-build validation (satellites 2 + 3)
# ---------------------------------------------------------------------------

class TestValidation:
    def test_windowed_aosoa_indivisible_vvl_named_error(self, rng):
        lat = Lattice((8, 8, 8))
        spec = _mixed_spec()
        f = jnp.zeros((2, lat.nsites), jnp.float32)
        r = jnp.zeros((1, lat.nsites), jnp.float32)
        t = Target("pallas_windowed", vvl=7, layout="aosoa", interpret=True)
        with pytest.raises(ValueError) as ei:
            launch(spec, t, f, r, lattice=lat,
                   consts={"alpha": 1.0, "w": jnp.ones((7,))})
        msg = str(ei.value)
        assert "mixed_layout" in msg and "vvl=7" in msg and "64" in msg

    def test_gathered_aosoa_any_vvl_valid(self, rng):
        """Remainder sites pad on gathered executors — vvl=7 is fine."""
        lat = Lattice((8, 8, 8))
        spec = _mixed_spec()
        f = jnp.asarray(rng.normal(size=(2, lat.nsites)), jnp.float32)
        r = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
        t = Target("pallas", vvl=7, layout="aosoa", interpret=True)
        out = launch(spec, t, f, r, lattice=lat,
                     consts={"alpha": 1.0, "w": jnp.ones((7,))})
        assert out[0].shape == (2, lat.nsites)

    def test_window_vmem_overflow_named_error(self):
        """Satellite 2: a plane_block window that exceeds the VMEM cap
        even at the smallest y-block (8 rows; a 16-row plane of 2**16
        lanes) fails at plan build, naming the worst field, the block and
        the byte count — not deep inside Mosaic."""
        lat = Lattice((4, 16, 2 ** 16))
        spec = _mixed_spec()
        f = jnp.zeros((2, lat.nsites), jnp.float32)
        r = jnp.zeros((1, lat.nsites), jnp.float32)
        t = Target("pallas_windowed", interpret=True,
                   tuning={"plane_block": 4})
        with pytest.raises(WindowVmemError) as ei:
            launch(spec, t, f, r, lattice=lat,
                   consts={"alpha": 1.0, "w": jnp.ones((7,))})
        msg = str(ei.value)
        assert "mixed_layout" in msg and "plane_block=4" in msg
        assert "y_block=8" in msg
        assert "f" in msg and "VMEM" not in msg.split()[:1]  # named error

    def test_launch_plan_skips_vmem_guard(self):
        """launch_plan must stay buildable over the cap so autotune can
        estimate-and-prune instead of crashing."""
        lat = Lattice((4, 16, 2 ** 16))
        spec = _mixed_spec()
        t = Target("pallas_windowed", interpret=True,
                   tuning={"plane_block": 4})
        plan = launch_plan(spec, t, lattice=lat)
        assert plan.y_block == 8
        assert plan.vmem_bytes_estimate() > 16 * 2 ** 20

    def test_aosoa_hbm_estimate_doubles(self):
        lat = Lattice((8, 8, 8))
        spec = _mixed_spec()
        soa = launch_plan(spec, Target("pallas_windowed", vvl=8,
                                       interpret=True), lattice=lat)
        aos = launch_plan(spec, Target("pallas_windowed", vvl=8,
                                       layout="aosoa", interpret=True),
                          lattice=lat)
        assert aos.hbm_bytes_estimate() == 2 * soa.hbm_bytes_estimate()


# ---------------------------------------------------------------------------
# autotune integration (satellite 1)
# ---------------------------------------------------------------------------

class TestAutotuneLayoutAxis:
    def test_default_space_grows_vvl_and_layout_axes(self):
        from repro.core.autotune import default_space

        def body(a):
            return 2.0 * a
        spec = KernelSpec(body, fields=(FieldSpec(3),), out=(3,), name="s")
        cands, _ = default_space(spec, Target("pallas", interpret=True),
                                 site_count=1024)
        labels = [c.label for c in cands]
        assert any("vvl=" in l and "layout" not in l for l in labels)
        assert any("layout=aosoa" in l for l in labels)

    def test_windowed_space_layout_vvls_divide_plane(self):
        from repro.core.autotune import default_space
        lat = Lattice((8, 8, 8))
        spec = _mixed_spec()
        cands, _ = default_space(
            spec, Target("pallas_windowed", interpret=True), lattice=lat)
        aosoa = [c for c in cands if c.layout == "aosoa"
                 and c.backend == "pallas_windowed"]
        assert aosoa, "windowed space must carry aosoa candidates"
        assert all(64 % c.vvl == 0 for c in aosoa)

    def test_candidate_zero_wins_ties(self, rng, tmp_path):
        """A constant-time fake timer makes every candidate tie — the
        tuner must keep the base target, not an exotic layout."""
        from repro.core.autotune import autotune
        lat = Lattice((8, 8, 8))
        spec = _mixed_spec()
        f = jnp.asarray(rng.normal(size=(2, lat.nsites)), jnp.float32)
        r = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
        tgt, report = autotune(
            spec, Target("xla", vvl=64), [f, r], lattice=lat,
            consts={"alpha": 1.0, "w": jnp.ones((7,))},
            timer=lambda t, run: 1.0, reps=1, warmup=0,
            cache_dir=str(tmp_path))
        assert report.best == report.results[0].candidate
        assert tgt.executor == "xla" and tgt.layout == "soa"

    def test_candidate_round_trips_layout_fields(self):
        from repro.core.autotune import Candidate
        c = Candidate("pallas", True, (("plane_block", 2),), 64, "aosoa")
        c2 = Candidate.from_dict(c.as_dict())
        assert c2 == c and c2.vvl == 64 and c2.layout == "aosoa"
        legacy = Candidate.from_dict({"backend": "xla"})   # v1/v2 entry
        assert legacy.vvl is None and legacy.layout is None
        assert "layout=aosoa" in c.label and "vvl=64" in c.label

    def test_vvl_invalid_candidate_pruned_not_fatal(self, rng, tmp_path):
        """An explicit-space candidate whose windowed-AoSoA vvl doesn't
        divide the plane count is pruned during measurement (the
        satellite-2/3 contract: named errors, autotune survives)."""
        from repro.core.autotune import Candidate, autotune
        lat = Lattice((8, 8, 8))
        spec = _mixed_spec()
        f = jnp.asarray(rng.normal(size=(2, lat.nsites)), jnp.float32)
        r = jnp.asarray(rng.normal(size=(1, lat.nsites)), jnp.float32)
        bad = Candidate("pallas_windowed", True, vvl=7, layout="aosoa")
        good = Candidate("pallas_windowed", True, vvl=16, layout="aosoa")
        tgt, report = autotune(
            spec, Target("xla", vvl=64), [f, r], lattice=lat,
            consts={"alpha": 1.0, "w": jnp.ones((7,))},
            space=[bad, good], timer=lambda t, run: 1.0, reps=1, warmup=0,
            check_outputs=True, cache_dir=str(tmp_path))
        assert any(bad.label == l for l, _ in report.pruned)
        assert {r_.candidate.label for r_ in report.results} >= \
            {good.label}
