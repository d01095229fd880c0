"""Stencils and streaming for the 3-D lattice, on the targetDP stencil layer.

The neighbourhood math is declared once as :class:`repro.core.Stencil`
descriptors attached to :class:`repro.core.KernelSpec` field roles and
executed by :func:`repro.tdp.launch` — the same single-source site
kernels run on every registered executor (paper portability contract,
extended from pointwise to stencil-shaped kernels).

Two execution regimes, one math:

* **single-device** — fully periodic; the stencil gather wraps every
  dimension (``halo=0``);
* **mesh-sharded** — slab decomposition along X over a named mesh axis;
  ghost planes travel by ``lax.ppermute`` (the JAX-native analogue of
  Ludwig's MPI halo swap; the paper's masked-copy machinery packs the
  boundary subset) and feed the stencil's ``halo=(h,0,0)`` window mode.
  Used inside ``shard_map`` by :mod:`repro.lb.sim`.

Gradients use the 6-point nearest-neighbour star:
  ∇φ_d  = (φ(+e_d) - φ(-e_d)) / 2
  ∇²φ   = Σ_d (φ(+e_d) + φ(-e_d)) - 6 φ
(adequate for the symmetric benchmark; ``STENCIL_GRAD_19PT`` declares the
19-point isotropic neighbourhood for a drop-in variant.)

The **fused step** (:data:`FUSED_SPEC`) is the paper-successor's
(1609.01479) key optimisation: one stencil launch computes
stream → φ moments → ∇φ/∇²φ → binary collision with *no* intermediate
full-lattice arrays.  Its g-field neighbourhood is the Minkowski
composition ``grad6 ∘ d3q19-pull`` (radius 2) — each site reads the
pre-stream populations that determine φ at itself and its six gradient
neighbours.  The **two-launch** variant (:data:`PHI_STREAM_SPEC` +
:data:`FUSED_TWO_SPEC`) trades that 57-offset gather for a 1-component
streamed-φ intermediate (ROADMAP stencil-memory stage (a)) while keeping
the identical accumulation order, so the two compute the same numbers
(tests hold them to each other under the cross-path contract,
:func:`repro.core.state.check_fields`).

Every spec here runs unchanged on every registered executor, including
the gather-free ``"pallas_windowed"`` one (stage (b)): its
``wants="halo_extended"`` capability swaps the launch prologue, never
the kernels — offsets the bodies address via the static ``_PULL_IDX`` /
``_FUSED_G_IDX`` slot tables are resolved in-kernel from the same
``Stencil`` descriptors (agreement with ``"xla"`` pinned by
``tests/test_windowed.py`` under the cross-path contract).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    FieldSpec,
    KernelSpec,
    Lattice,
    STENCIL_D3Q19_PULL,
    STENCIL_GRAD_6PT,
    STENCIL_GRAD_19PT,  # noqa: F401 — re-exported config switch
    Target,
    as_target,
    tdp_launch,
)
from repro.kernels.lb_collision import CV, NVEL, collision_site_kernel

# grid arrays are (ncomp, X, Y, Z); spatial axes are 1, 2, 3
_SPATIAL = (1, 2, 3)

_CVI = CV.astype(int)

# slot of the upstream neighbour -c_q in the pull stencil (== q by
# construction; resolved through Stencil.index so the kernels stay correct
# under any offset ordering)
_PULL_IDX = tuple(STENCIL_D3Q19_PULL.index(tuple(-_CVI[q]))
                  for q in range(NVEL))

# gradient star directions, in STENCIL_GRAD_6PT slot order:
# (centre, +x, -x, +y, -y, +z, -z)
_DIRS = STENCIL_GRAD_6PT.offsets

#: g-field neighbourhood of the fused step: populations at d - c_q for every
#: gradient direction d and velocity c_q (radius 2).
STENCIL_FUSED_G = STENCIL_GRAD_6PT.compose(STENCIL_D3Q19_PULL, name="fused_g")

# _FUSED_G_IDX[d][q]: slot of offset (dirs[d] - c_q) in STENCIL_FUSED_G —
# where population q that will stream onto site+dirs[d] sits pre-stream.
_FUSED_G_IDX = tuple(
    tuple(STENCIL_FUSED_G.index(tuple(np.add(d, -_CVI[q])))
          for q in range(NVEL))
    for d in _DIRS)

#: collision TARGET_CONST names shared by the fused specs
_COLLISION_CONSTS = ("w", "c", "A", "B", "kappa", "tau", "tau_phi", "gamma")


# ---------------------------------------------------------------------------
# site kernels (single source; static slot indices — Pallas-legal)
# ---------------------------------------------------------------------------

def stream_site_kernel(f_nb):
    """Pull streaming over one chunk: ``f_nb (19, 19, V)`` neighbour stack
    (slot i = populations at site + pull offset i) → streamed ``(19, V)``."""
    return jnp.stack([f_nb[_PULL_IDX[q], q] for q in range(NVEL)])


def _grad6_from_p(p):
    """∇φ (3, V) and ∇²φ (V,) from φ at the 7 grad-star slots (p[0] =
    centre, then +x,-x,+y,-y,+z,-z).  One accumulation order, shared by the
    plain, fused and two-launch kernels — it must stay bit-identical between
    them (and with the historical roll-based implementation) for the
    fused==unfused trajectory guarantee."""
    grad = 0.5 * jnp.stack([p[1] - p[2], p[3] - p[4], p[5] - p[6]])
    lap = -6.0 * p[0]
    lap = lap + p[1] + p[2]
    lap = lap + p[3] + p[4]
    lap = lap + p[5] + p[6]
    return grad, lap


def grad6_site_kernel(phi_nb):
    """6-point ∇φ and ∇²φ over one chunk: ``phi_nb (7, 1, V)`` →
    ``((3, V), (1, V))``."""
    grad, lap = _grad6_from_p(phi_nb[:, 0])
    return grad, lap[None]


def fused_site_kernel(f_nb, g_nb, *, w=None, c=None, A=0.0625, B=0.0625,
                      kappa=0.04, tau=1.0, tau_phi=1.0, gamma=1.0):
    """Fused stream → moments → gradients → binary collision, one chunk.

    Args:
      f_nb: (19, 19, V) fluid populations at the pull offsets.
      g_nb: (noffsets, 19, V) order-parameter populations at the composed
        ``STENCIL_FUSED_G`` offsets.
      w, c, A..gamma: the collision TARGET_CONSTs (see
        :func:`repro.kernels.lb_collision.collision_site_kernel`).

    Returns post-collision ``(f', g')`` chunks, both (19, V) — the
    *pre-stream* state of the next step.
    """
    f_s = jnp.stack([f_nb[_PULL_IDX[q], q] for q in range(NVEL)])
    g_s = jnp.stack([g_nb[_FUSED_G_IDX[0][q], q] for q in range(NVEL)])

    # φ of the streamed g at the site and its 6 gradient neighbours —
    # φ(x+d) = Σ_q g(x + d - c_q); never materialised outside the chunk.
    def phi_at(d):
        acc = g_nb[_FUSED_G_IDX[d][0], 0]
        for q in range(1, NVEL):
            acc = acc + g_nb[_FUSED_G_IDX[d][q], q]
        return acc

    p = [phi_at(d) for d in range(len(_DIRS))]         # 7 × (V,)
    grad, lap = _grad6_from_p(p)
    return collision_site_kernel(
        f_s, g_s, p[0][None], grad, lap[None], w=w, c=c, A=A, B=B,
        kappa=kappa, tau=tau, tau_phi=tau_phi, gamma=gamma)


fused_site_kernel.__tdp_site_kernel__ = True


def streamed_phi_site_kernel(g_nb):
    """Launch A of the two-launch fused step: φ of the *streamed* g at one
    site, ``g_nb (19, 19, V)`` pull stack → ``(1, V)``.

    Accumulates in ascending q order — the exact order
    :func:`fused_site_kernel`'s ``phi_at`` uses, so both fused modes
    produce bit-identical φ."""
    acc = g_nb[_PULL_IDX[0], 0]
    for q in range(1, NVEL):
        acc = acc + g_nb[_PULL_IDX[q], q]
    return acc[None]


def fused_two_site_kernel(f_nb, g_nb, phis_nb, *, w=None, c=None, A=0.0625,
                          B=0.0625, kappa=0.04, tau=1.0, tau_phi=1.0,
                          gamma=1.0):
    """Launch B of the two-launch fused step: stream + collide, reading the
    pre-streamed φ intermediate through the 7-point gradient star.

    Args:
      f_nb / g_nb: (19, 19, V) populations at the pull offsets.
      phis_nb: (7, 1, V) streamed-φ values at the gradient-star slots
        (launch A's output) — replaces the one-launch kernel's 57-offset
        g gather.
    """
    f_s = jnp.stack([f_nb[_PULL_IDX[q], q] for q in range(NVEL)])
    g_s = jnp.stack([g_nb[_PULL_IDX[q], q] for q in range(NVEL)])
    p = [phis_nb[i, 0] for i in range(len(_DIRS))]
    grad, lap = _grad6_from_p(p)
    return collision_site_kernel(
        f_s, g_s, p[0][None], grad, lap[None], w=w, c=c, A=A, B=B,
        kappa=kappa, tau=tau, tau_phi=tau_phi, gamma=gamma)


def phi_moment_site_kernel(g):
    """Order-parameter moment over one chunk: φ = Σ_q g_q,
    ``g (19, V)`` → ``(1, V)`` (the unfused pipeline's site-local moment
    pass, as a declared pointwise kernel)."""
    return jnp.sum(g, axis=0, keepdims=True)


# ---------------------------------------------------------------------------
# kernel specs — the declarative launch surface (what ops/sim dispatch on)
# ---------------------------------------------------------------------------

STREAM_SPEC = KernelSpec(
    stream_site_kernel,
    fields=(FieldSpec(ncomp=NVEL, stencil=STENCIL_D3Q19_PULL, name="f"),),
    out=NVEL)

GRAD6_SPEC = KernelSpec(
    grad6_site_kernel,
    fields=(FieldSpec(ncomp=1, stencil=STENCIL_GRAD_6PT, name="phi"),),
    out=(3, 1))

FUSED_SPEC = KernelSpec(
    fused_site_kernel,
    fields=(FieldSpec(ncomp=NVEL, stencil=STENCIL_D3Q19_PULL, name="f"),
            FieldSpec(ncomp=NVEL, stencil=STENCIL_FUSED_G, name="g")),
    out=(NVEL, NVEL), consts=_COLLISION_CONSTS)

PHI_STREAM_SPEC = KernelSpec(
    streamed_phi_site_kernel,
    fields=(FieldSpec(ncomp=NVEL, stencil=STENCIL_D3Q19_PULL, name="g"),),
    out=1)

FUSED_TWO_SPEC = KernelSpec(
    fused_two_site_kernel,
    fields=(FieldSpec(ncomp=NVEL, stencil=STENCIL_D3Q19_PULL, name="f"),
            FieldSpec(ncomp=NVEL, stencil=STENCIL_D3Q19_PULL, name="g"),
            FieldSpec(ncomp=1, stencil=STENCIL_GRAD_6PT, name="phi_streamed")),
    out=(NVEL, NVEL), consts=_COLLISION_CONSTS)

MOMENT_SPEC = KernelSpec(
    phi_moment_site_kernel,
    fields=(FieldSpec(ncomp=NVEL, name="g"),),
    out=1)

COLLIDE_SPEC = KernelSpec(
    collision_site_kernel,
    fields=(FieldSpec(ncomp=NVEL, name="f"),
            FieldSpec(ncomp=NVEL, name="g"),
            FieldSpec(ncomp=1, name="phi"),
            FieldSpec(ncomp=3, name="gradphi"),
            FieldSpec(ncomp=1, name="del2phi")),
    out=(NVEL, NVEL), consts=_COLLISION_CONSTS)


# ---------------------------------------------------------------------------
# grid-level wrappers (single device: fully periodic)
# ---------------------------------------------------------------------------

def gradients(phi: jax.Array, *, target: Target | str | None = None,
              vvl: int | None = None) -> tuple[jax.Array, jax.Array]:
    """∇φ and ∇²φ of a scalar grid ``(X, Y, Z)`` → ``(3, X, Y, Z)``, ``(X, Y, Z)``."""
    gs = phi.shape
    lat = Lattice(gs)
    grad, lap = tdp_launch(GRAD6_SPEC, as_target(target, vvl=vvl),
                           phi.reshape(1, lat.nsites), lattice=lat)
    return grad.reshape(3, *gs), lap.reshape(gs)


def stream(dist: jax.Array, *, target: Target | str | None = None,
           vvl: int | None = None) -> jax.Array:
    """Periodic streaming of ``(19, X, Y, Z)``: f_q(x) ← f_q(x - c_q)."""
    gs = dist.shape[1:]
    lat = Lattice(gs)
    out = tdp_launch(STREAM_SPEC, as_target(target, vvl=vvl),
                     dist.reshape(NVEL, lat.nsites), lattice=lat)
    return out.reshape(NVEL, *gs)


# ---------------------------------------------------------------------------
# mesh-sharded path
# ---------------------------------------------------------------------------
#
# The slab-decomposition glue (ppermute ghost exchange + per-launch halo
# widths) that used to live here is owned by the Program layer now:
# repro.core.program back-propagates one exchange schedule per step
# (`Program.schedule`) and performs the exchange in `_exchange_dim0` —
# repro.lb.programs declares the LB step graphs it applies to.


def halo_plane_mask(shape: tuple[int, int, int]) -> np.ndarray:
    """Boolean site mask selecting the X-boundary planes — feeds the paper's
    ``copy*Masked`` functions when staging boundary data through the host."""
    m = np.zeros(shape, dtype=bool)
    m[0, :, :] = True
    m[-1, :, :] = True
    return m.reshape(-1)
