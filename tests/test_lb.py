"""Lattice-Boltzmann application tests: physics + implementation equality.

The paper's motivating application.  Conservation laws are the integration
oracle: BGK collision + streaming conserves total mass exactly and the
binary order parameter exactly; momentum is conserved up to the
free-energy forcing (which sums to ~0 over a periodic box).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import check_fields
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim
from repro.lb import baseline, stencil
from repro.kernels.lb_collision import CV, NVEL, WEIGHTS


class TestStencil:
    def test_stream_conserves_and_shifts(self, rng):
        f = jnp.asarray(rng.normal(size=(NVEL, 4, 4, 4)), jnp.float32)
        fs = stencil.stream(f)
        # streaming is an exact permutation — sum in f64 so the assertion
        # is not at the mercy of float32 reduction order
        np.testing.assert_allclose(np.asarray(fs, np.float64).sum(),
                                   np.asarray(f, np.float64).sum(),
                                   rtol=1e-12)
        # q=0 is the rest particle: unmoved
        np.testing.assert_array_equal(fs[0], f[0])
        # each q shifted by its velocity (bit-exact: a gather, no math)
        for q in (1, 5, 10):
            want = np.roll(np.asarray(f[q]), shift=tuple(int(c) for c in CV[q]),
                           axis=(0, 1, 2))
            np.testing.assert_array_equal(np.asarray(fs[q]), want)

    def test_gradients_of_linear_field(self):
        """∇φ of a linear ramp is the slope; ∇²φ is 0 (periodic interior)."""
        x = np.arange(8.0)
        phi = jnp.asarray(np.broadcast_to(
            np.sin(2 * np.pi * x / 8)[:, None, None], (8, 8, 8)), jnp.float32)
        grad, del2 = stencil.gradients(phi)
        # numerical vs analytic derivative of sin
        want = (2 * np.pi / 8) * np.cos(2 * np.pi * x / 8)
        got = np.asarray(grad[0, :, 4, 4])
        # 2nd-order central difference of sin has a known sinc prefactor
        pref = np.sin(2 * np.pi / 8) / (2 * np.pi / 8)
        np.testing.assert_allclose(got, pref * want, rtol=1e-4, atol=1e-5)
        assert abs(float(grad[1].sum())) < 1e-3  # no y-gradient


class TestConservation:
    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_mass_and_phi_conserved(self, backend):
        sim = BinaryFluidSim((8, 8, 8), backend=backend, vvl=64)
        st = sim.init_spinodal(seed=1, noise=0.05)
        obs0 = sim.observables(st)
        st = sim.step(st, 10)
        obs1 = sim.observables(st)
        assert not obs1["nan"]
        np.testing.assert_allclose(obs1["mass"], obs0["mass"], rtol=1e-5)
        np.testing.assert_allclose(obs1["phi_total"], obs0["phi_total"],
                                   rtol=1e-5, atol=1e-4)

    def test_momentum_near_zero(self):
        """Periodic quench at rest: net momentum stays ~0 (forcing sums 0)."""
        sim = BinaryFluidSim((8, 8, 8))
        st = sim.init_spinodal(seed=2)
        st = sim.step(st, 10)
        c = jnp.asarray(CV, jnp.float32)
        mom = jnp.einsum("qd,qxyz->d", c, st.f)
        assert float(jnp.abs(mom).max()) < 1e-2

    def test_spinodal_coarsens(self):
        """Phase separation: φ variance grows from a symmetric quench and
        domains approach φ=±1 (deep quench for CPU-friendly timescales)."""
        p = LBParams(A=0.125, B=0.125, kappa=0.02)
        sim = BinaryFluidSim((12, 12, 12), params=p)
        st = sim.init_spinodal(seed=3, noise=0.05)
        v0 = sim.observables(st)["phi_var"]
        st = sim.run_scanned(st, 200)
        obs = sim.observables(st)
        assert not obs["nan"]
        assert obs["phi_var"] > 50 * v0          # domains formed
        assert obs["phi_max"] > 0.5 and obs["phi_min"] < -0.5

    def test_droplet_interface(self):
        """tanh droplet stays a droplet (φ bounds don't blow up)."""
        sim = BinaryFluidSim((12, 12, 12))
        st = sim.init_droplet()
        st = sim.step(st, 20)
        obs = sim.observables(st)
        assert not obs["nan"]
        assert -1.2 < obs["phi_min"] < -0.5 and 0.5 < obs["phi_max"] < 1.2


class TestFusedStep:
    """The fused stream→gradient→collide launch is a drop-in for the
    4-launch unfused pipeline: the same trajectory, conservation intact."""

    @pytest.mark.parametrize("want_mode,got_mode", [
        (False, "one_launch"), ("one_launch", "two_launch"),
        (False, "two_launch")],
        ids=["fused_matches_unfused", "two_launch_matches_one_launch",
             "two_launch_matches_unfused"])
    def test_trajectories_match(self, want_mode, got_mode):
        """10 steps at 16³ agree across fusion modes under the cross-path
        contract; the two-launch step streams a 1-component φ
        intermediate instead of gathering g's 57 offsets (ROADMAP
        stencil-memory stage (a))."""
        p = LBParams(A=0.125, B=0.125, kappa=0.02)
        a = BinaryFluidSim((16, 16, 16), params=p, fused=want_mode)
        b = BinaryFluidSim((16, 16, 16), params=p, fused=got_mode)
        st0 = a.init_spinodal(seed=3, noise=0.05)
        ua = a.step(st0, 10)
        ub = b.step(st0, 10)
        check_fields({"f": ua.f, "g": ua.g}, {"f": ub.f, "g": ub.g})

    @pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
    def test_two_launch_conserves(self, backend):
        sim = BinaryFluidSim((8, 8, 8), backend=backend, vvl=64,
                             fused="two_launch")
        st = sim.init_spinodal(seed=1, noise=0.05)
        obs0 = sim.observables(st)
        st = sim.step(st, 10)
        obs1 = sim.observables(st)
        assert not obs1["nan"]
        np.testing.assert_allclose(obs1["mass"], obs0["mass"], rtol=1e-5)
        np.testing.assert_allclose(obs1["phi_total"], obs0["phi_total"],
                                   rtol=1e-5, atol=1e-4)

    def test_fused_mode_validation(self):
        with pytest.raises(ValueError, match="fused"):
            BinaryFluidSim((8, 8, 8), fused="three_launch")

    def test_fused_scanned_matches_stepped(self):
        sim = BinaryFluidSim((8, 8, 8), fused=True)
        st = sim.init_spinodal(seed=4)
        a = sim.step(st, 6)
        b = sim.run_scanned(st, 6)
        # one program driven two ways: a step loop and run's scan
        np.testing.assert_array_equal(np.asarray(a.f), np.asarray(b.f))
        np.testing.assert_array_equal(np.asarray(a.g), np.asarray(b.g))

    @pytest.mark.parametrize("backend,vvl", [("xla", 128),
                                             ("pallas_interpret", 64)])
    def test_fused_conserves(self, backend, vvl):
        sim = BinaryFluidSim((8, 8, 8), backend=backend, vvl=vvl, fused=True)
        st = sim.init_spinodal(seed=1, noise=0.05)
        obs0 = sim.observables(st)
        st = sim.step(st, 10)
        obs1 = sim.observables(st)
        assert not obs1["nan"]
        np.testing.assert_allclose(obs1["mass"], obs0["mass"], rtol=1e-5)
        np.testing.assert_allclose(obs1["phi_total"], obs0["phi_total"],
                                   rtol=1e-5, atol=1e-4)


class TestBaselineEquivalence:
    """Paper Fig. 1: "original" AoS innermost-loop code vs targetDP —
    the same numerics in a different execution structure, held to the
    cross-path contract."""

    def test_original_matches_targetdp(self, rng):
        """AoS 'original code' path == SoA targetDP path after transpose."""
        p = LBParams()
        n = 128
        f = jnp.asarray(0.05 * rng.normal(size=(19, n)) + 1 / 19., jnp.float32)
        g = jnp.asarray(0.05 * rng.normal(size=(19, n)), jnp.float32)
        phi = g.sum(0, keepdims=True)
        gp = jnp.asarray(0.01 * rng.normal(size=(3, n)), jnp.float32)
        d2 = jnp.asarray(0.01 * rng.normal(size=(1, n)), jnp.float32)
        fo_b, go_b = baseline.collide_aos(f.T, g.T, phi[0], gp.T, d2[0], p)
        from repro.kernels import ops
        fo_t, go_t = ops.lb_collision(f, g, phi, gp, d2, **p.as_kwargs())
        check_fields({"f": fo_t, "g": go_t}, {"f": fo_b.T, "g": go_b.T})

    def test_stream_aos_matches_soa(self, rng):
        f = jnp.asarray(rng.normal(size=(NVEL, 4, 4, 4)), jnp.float32)
        a = stencil.stream(f)
        b = baseline.stream_aos(jnp.moveaxis(f, 0, -1))
        check_fields({"f": a}, {"f": jnp.moveaxis(b, -1, 0)})

    def test_scanned_run_matches_stepped(self):
        sim = BinaryFluidSim((8, 8, 8))
        st = sim.init_spinodal(seed=4)
        a = sim.step(st, 5)
        b = sim.run_scanned(st, 5)
        # one program driven two ways: a step loop and run's scan
        np.testing.assert_array_equal(np.asarray(a.f), np.asarray(b.f))
        np.testing.assert_array_equal(np.asarray(a.g), np.asarray(b.g))
