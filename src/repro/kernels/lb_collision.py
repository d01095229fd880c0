"""D3Q19 binary-fluid lattice-Boltzmann collision — the paper's benchmark.

This is the "binary collision" kernel of §IV: a BGK collision of two
distributions (f for the fluid, g for the composition order parameter φ)
with a free-energy force, site-local over 19+19+5 components per site.

Physics (force-based binary model; Swift/Kendon-family, Guo forcing):

* moments:        ρ = Σᵢ fᵢ,   ρu = Σᵢ fᵢcᵢ + F/2,   φ = Σᵢ gᵢ
* free energy:    μ = -A φ + B φ³ - κ ∇²φ      (symmetric double well)
* force:          F = μ ∇φ
* equilibria:     fᵢᵉq = wᵢ ρ (1 + 3cᵢ·u + 9/2 (cᵢ·u)² - 3/2 u²)
                  gᵢᵉq = wᵢ (3Γμ + 3φ cᵢ·u)  (i≥1);  g₀ᵉq = φ - Σ_{i≥1} gᵢᵉq
* collision:      fᵢ' = fᵢ - (fᵢ - fᵢᵉq)/τ + (1 - 1/2τ) wᵢ (3(cᵢ-u) + 9cᵢ(cᵢ·u))·F
                  gᵢ' = gᵢ - (gᵢ - gᵢᵉq)/τ_φ

Mass (Σf) is conserved exactly; momentum changes by exactly F per site;
Σg = φ is conserved exactly — tests assert all three.

The paper's point: the innermost model-dictated extents (19 momenta,
3 dimensions) do not fill vector hardware; the site-chunk axis (VVL) does.
Here the kernel body operates on ``(ncomp, VVL)`` chunks — every op
vectorises over the trailing VVL lanes; the 19/3-extent contractions become
small ``(19,3)``-matrix ops on sublanes.

Three realisations, single source:
  * :func:`collision_site_kernel` — the targetDP site kernel (runs under the
    generic jnp and Pallas executors);
  * :func:`lb_collision_pallas` — dedicated ``pl.pallas_call`` with explicit
    BlockSpecs and the chemical potential **fused** into the collision
    (one HBM round-trip saved: μ never materialises);
  * ``repro.kernels.ref.lb_collision_ref`` — pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# ---------------------------------------------------------------------------
# D3Q19 velocity set
# ---------------------------------------------------------------------------
# index 0: rest; 1..6: axis vectors; 7..18: face diagonals.

CV = np.array(
    [[0, 0, 0],
     [1, 0, 0], [-1, 0, 0], [0, 1, 0], [0, -1, 0], [0, 0, 1], [0, 0, -1],
     [1, 1, 0], [1, -1, 0], [-1, 1, 0], [-1, -1, 0],
     [1, 0, 1], [1, 0, -1], [-1, 0, 1], [-1, 0, -1],
     [0, 1, 1], [0, 1, -1], [0, -1, 1], [0, -1, -1]],
    dtype=np.float64,
)
WEIGHTS = np.array([1.0 / 3.0] + [1.0 / 18.0] * 6 + [1.0 / 36.0] * 12,
                   dtype=np.float64)
NVEL = 19
NDIM = 3

assert CV.shape == (NVEL, NDIM)
assert abs(WEIGHTS.sum() - 1.0) < 1e-15
assert np.allclose(WEIGHTS @ CV, 0.0)
assert np.allclose(np.einsum("qa,qb,q->ab", CV, CV, WEIGHTS), np.eye(3) / 3.0)


# ---------------------------------------------------------------------------
# single-source site kernel (targetDP)
# ---------------------------------------------------------------------------

def collision_site_kernel(f, g, phi, gradphi, del2phi, *,
                          w=None, c=None, A=0.0625, B=0.0625, kappa=0.04,
                          tau=1.0, tau_phi=1.0, gamma=1.0):
    """Binary collision over one VVL chunk.

    Args:
      f: (19, V) fluid distribution chunk.
      g: (19, V) order-parameter distribution chunk.
      phi: (1, V) order parameter (Σg, precomputed by the moment pass).
      gradphi: (3, V) ∇φ (stencil pass).
      del2phi: (1, V) ∇²φ (stencil pass).
      w, c: TARGET_CONST weight vector (19,) and velocity set (19, 3).
      A, B, kappa, tau, tau_phi, gamma: scalar TARGET_CONSTs.

    Returns:
      (f', g') chunks, both (19, V).
    """
    dt = f.dtype
    w = w.astype(dt)[:, None]                      # (19, 1)
    c = c.astype(dt)                               # (19, 3)
    phi_ = phi[0]                                  # (V,)
    d2 = del2phi[0]

    # chemical potential (fused — μ never touches HBM)
    mu = -A * phi_ + B * phi_ * phi_ * phi_ - kappa * d2      # (V,)
    force = mu[None, :] * gradphi                              # (3, V)

    # The velocity-set contractions run in full float32: on a TPU a
    # float32 dot at default precision is one bfloat16 pass (~3 digits),
    # which moves f by ~1e-4 in 20 steps.  On the CPU this changes nothing.
    hi = jax.lax.Precision.HIGHEST
    rho = jnp.sum(f, axis=0)                                   # (V,)
    mom = jnp.einsum("qd,qv->dv", c, f, precision=hi)          # (3, V)
    u = (mom + 0.5 * force) / rho[None, :]                     # (3, V)

    cu = jnp.einsum("qd,dv->qv", c, u, precision=hi)           # (19, V)
    usq = jnp.sum(u * u, axis=0)                               # (V,)
    feq = w * rho[None, :] * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq[None, :])

    cf = jnp.einsum("qd,dv->qv", c, force, precision=hi)       # (19, V)
    uf = jnp.sum(u * force, axis=0)                            # (V,)
    fterm = (1.0 - 0.5 / tau) * w * (3.0 * (cf - uf[None, :]) + 9.0 * cu * cf)
    f_out = f - (f - feq) / tau + fterm

    gt = w * (3.0 * gamma * mu[None, :] + 3.0 * phi_[None, :] * cu)  # (19, V)
    g0 = phi_ - (jnp.sum(gt, axis=0) - gt[0])                  # rest population
    geq = jnp.concatenate([g0[None, :], gt[1:]], axis=0)
    g_out = g - (g - geq) / tau_phi
    return f_out, g_out


collision_site_kernel.__tdp_site_kernel__ = True


# ---------------------------------------------------------------------------
# dedicated Pallas kernel (explicit BlockSpec VMEM tiling)
# ---------------------------------------------------------------------------

def _collision_body(f_ref, g_ref, phi_ref, gphi_ref, d2_ref, w_ref, c_ref,
                    fout_ref, gout_ref, *, scalars):
    f_out, g_out = collision_site_kernel(
        f_ref[...], g_ref[...], phi_ref[...], gphi_ref[...], d2_ref[...],
        w=w_ref[...].reshape(NVEL), c=c_ref[...], **scalars)
    fout_ref[...] = f_out.astype(fout_ref.dtype)
    gout_ref[...] = g_out.astype(gout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("vvl", "interpret", "A", "B",
                                             "kappa", "tau", "tau_phi", "gamma"))
def lb_collision_pallas(f, g, phi, gradphi, del2phi, *, vvl: int = 128,
                        interpret: bool = False,
                        A: float = 0.0625, B: float = 0.0625,
                        kappa: float = 0.04, tau: float = 1.0,
                        tau_phi: float = 1.0, gamma: float = 1.0):
    """Fused binary collision over SoA arrays ``(ncomp, nsites)``.

    VMEM per grid step ≈ (19+19+1+3+1+19+19)·VVL·4 B ≈ 324·VVL B:
    VVL=4096 → ~1.3 MiB, comfortably inside 16 MiB VMEM with double
    buffering; the benchmark sweeps VVL (the paper's tuning experiment).
    """
    n = f.shape[-1]
    n_pad = -(-n // vvl) * vvl
    nchunks = n_pad // vvl
    dt = f.dtype

    def pad(x):
        if n_pad == n:
            return x
        # Pad with safe values: rho=Σf=19 on w-weighted unit f keeps the
        # 1/rho finite in the padded region (results are sliced away).
        fill = 1.0 if x is f else 0.0
        return jnp.pad(x, ((0, 0), (0, n_pad - n)), constant_values=fill)

    fp, gp, php, gpp, d2p = (pad(x) for x in (f, g, phi, gradphi, del2phi))
    w_arr = jnp.asarray(WEIGHTS, dtype=dt).reshape(1, NVEL)
    c_arr = jnp.asarray(CV, dtype=dt)

    scalars = dict(A=A, B=B, kappa=kappa, tau=tau, tau_phi=tau_phi, gamma=gamma)
    body = functools.partial(_collision_body, scalars=scalars)

    site_block = lambda ncomp: pl.BlockSpec((ncomp, vvl), lambda i: (0, i))
    const_block = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))

    fo, go = pl.pallas_call(
        body,
        grid=(nchunks,),
        in_specs=[site_block(NVEL), site_block(NVEL), site_block(1),
                  site_block(NDIM), site_block(1),
                  const_block((1, NVEL)), const_block((NVEL, NDIM))],
        out_specs=[site_block(NVEL), site_block(NVEL)],
        out_shape=[jax.ShapeDtypeStruct((NVEL, n_pad), dt),
                   jax.ShapeDtypeStruct((NVEL, n_pad), dt)],
        interpret=interpret,
        name=f"lb_collision_d3q19_vvl{vvl}",
    )(fp, gp, php, gpp, d2p, w_arr, c_arr)
    return fo[:, :n], go[:, :n]
