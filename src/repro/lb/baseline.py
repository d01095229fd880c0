"""The paper's "original code" baseline: AoS layout, model-dictated extents.

Before targetDP, Ludwig's collision loops had innermost extents of 19 (the
discrete momenta) or 3 (spatial dimensions) — extents the compiler cannot
map onto vector hardware (Fig. 1's lower bars).  This module reproduces that
structure faithfully in JAX: the lattice field is **AoS** ``(X, Y, Z, 19)``
so every contraction runs over the *minor* axis of extent 19/3 and the
site axis is not exposed as a vectorisable innermost dimension.

It computes the targetDP path's numbers in another execution structure
(tests hold it to that path, after layout transposition, under the
cross-path contract ``repro.core.check_fields``) and exists as the
paper's Fig. 1 "original code" baseline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lb_collision import CV, NVEL, WEIGHTS
from .params import LBParams


@functools.partial(jax.jit, static_argnames=("params",))
def collide_aos(f, g, phi, gradphi, del2phi, params: LBParams):
    """AoS collision: f, g ``(..., 19)``; gradphi ``(..., 3)``; phi, del2phi ``(...)``.

    Contractions deliberately run over the trailing 19-/3-extent axes —
    the exact structure the paper identifies as vector-hostile.
    """
    w = jnp.asarray(WEIGHTS, f.dtype)                    # (19,)
    c = jnp.asarray(CV, f.dtype)                         # (19, 3)
    A, B, kappa = params.A, params.B, params.kappa
    tau, tau_phi, gamma = params.tau, params.tau_phi, params.gamma

    mu = -A * phi + B * phi ** 3 - kappa * del2phi       # (...)
    force = mu[..., None] * gradphi                      # (..., 3)

    hi = jax.lax.Precision.HIGHEST         # float32 contractions on a TPU
    rho = f.sum(-1)                                      # (...)
    mom = jnp.einsum("...q,qd->...d", f, c, precision=hi)  # (..., 3)
    u = (mom + 0.5 * force) / rho[..., None]             # (..., 3)

    cu = jnp.einsum("...d,qd->...q", u, c, precision=hi)  # (..., 19)
    usq = (u * u).sum(-1)                                # (...)
    feq = w * rho[..., None] * (1 + 3 * cu + 4.5 * cu ** 2
                                - 1.5 * usq[..., None])
    cf = jnp.einsum("...d,qd->...q", force, c, precision=hi)  # (..., 19)
    uf = (u * force).sum(-1)                             # (...)
    fterm = (1 - 0.5 / tau) * w * (3 * (cf - uf[..., None]) + 9 * cu * cf)
    f_out = f - (f - feq) / tau + fterm

    gt = w * (3 * gamma * mu[..., None] + 3 * phi[..., None] * cu)
    g0 = phi - (gt.sum(-1) - gt[..., 0])
    geq = jnp.concatenate([g0[..., None], gt[..., 1:]], axis=-1)
    g_out = g - (g - geq) / tau_phi
    return f_out, g_out


def stream_aos(dist: jax.Array) -> jax.Array:
    """Streaming for AoS ``(X, Y, Z, 19)``."""
    shifted = [
        jnp.roll(dist[..., q], shift=tuple(int(x) for x in CV[q]), axis=(0, 1, 2))
        for q in range(NVEL)
    ]
    return jnp.stack(shifted, axis=-1)
