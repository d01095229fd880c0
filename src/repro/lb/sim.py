"""Binary-fluid simulation driver (the end-to-end Ludwig-style application).

One timestep:
  1. moment pass:   φ = Σ_i g_i                      (site-local)
  2. stencil pass:  ∇φ, ∇²φ                          (nearest-neighbour)
  3. collision:     targetDP kernel (f, g, φ, ∇φ, ∇²φ) → (f', g')   ← hot spot
  4. streaming:     f'_q(x+c_q) ← f'_q(x)            (shift + halo)

Since the ``tdp.Program`` redesign this driver is a *thin assembly*: the
step shapes live in :mod:`repro.lb.programs` as declarative stage graphs
and everything that used to be hand-wired here — per-launch halo
exchange, executor fallbacks for pointwise launches, intermediate
buffers, ``lax.scan`` stepping — is owned by
:class:`repro.core.Program`:

* the halo schedule is back-propagated per step (**one** ghost exchange
  round per field per step, shared across stages, under ``shard_map``);
* pointwise stages route to the ``"xla"`` executor automatically when
  the requested target is stencil-only (``wants="halo_extended"``);
* :meth:`BinaryFluidSim.run` executes n steps under one jitted
  ``lax.scan`` (``donate=True`` ping-pongs the field buffers).

``fused`` selects the hot-loop fusion strategy (all trajectories agree
state for state under the cross-path contract,
:func:`repro.core.state.check_fields`):

* ``False`` — the 4-launch unfused pipeline above (one 5-stage Program).
* ``"one_launch"`` (or ``True``) — one stencil stage per step
  (stream → φ moments → ∇φ/∇²φ → collide; no intermediate full-lattice
  arrays), over the radius-2 composed g-neighbourhood.
* ``"two_launch"`` — ROADMAP stencil-memory stage (a): launch A streams
  g's moments into a 1-component φ intermediate, launch B (radius-1
  stencils only) streams/collides against it.

In every fused mode the iterated state is the pre-stream populations
w = collide(u), since (stream∘collide)ⁿ = stream ∘ (collide∘stream)ⁿ⁻¹ ∘
collide — the prologue (collide) and epilogue (stream) run once as their
own Programs, so fused and unfused trajectories agree state for state
(each holds to the benchmark's plain float32 reference,
``tests/test_reference.py``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import Target, executor_wants
from repro.kernels import ops
from repro.kernels.lb_collision import NVEL, WEIGHTS
from . import programs as lbp
from .params import LBParams

_FUSED_MODES = (False, "one_launch", "two_launch")


@dataclass
class LBState:
    f: jax.Array          # (19, X, Y, Z)
    g: jax.Array          # (19, X, Y, Z)
    step: int = 0

    @property
    def grid_shape(self) -> tuple[int, int, int]:
        return self.f.shape[1:]


class BinaryFluidSim:
    """Spinodal-decomposition / droplet simulation of a binary mixture.

    The compiled step graphs are exposed as ``sim.programs`` — a dict of
    :class:`repro.core.CompiledProgram`: ``{"step": ...}`` for the
    unfused regime, ``{"collide": ..., "fused": ..., "stream": ...}``
    for the fused ones (prologue / hot-loop body / epilogue).
    """

    def __init__(self, grid_shape=(32, 32, 32), params: LBParams | None = None,
                 *, target: Target | str | None = None,
                 backend: str = "xla", vvl: int = 128,
                 mesh: Mesh | None = None,
                 shard_axis: str | tuple[str, ...] = "data",
                 overlap: bool | None = None,
                 fused: bool | str = False, dtype=jnp.float32):
        self.grid_shape = tuple(int(s) for s in grid_shape)
        self.params = params or LBParams()
        if target is None:
            target = Target(backend, vvl=vvl, mesh=mesh,
                            shard_axis=shard_axis if mesh is not None
                            else None)
        else:
            target = ops.op_target(target, default_vvl=vvl)
            if mesh is None:
                mesh = target.mesh
        self.target = target
        # Program compilation routes pointwise stages to xla under a
        # stencil-only target, but the *unfused* pipeline is
        # pointwise-dominated (collision) — requesting a stencil-only
        # executor for it would silently benchmark xla, so fail fast.
        try:
            stencil_only = executor_wants(target.executor) == "halo_extended"
        except ValueError:
            stencil_only = False    # custom executor registered later
        if stencil_only and not fused:
            raise ValueError(
                f"target executor {target.executor!r} is stencil-only "
                f"(wants='halo_extended'); it only runs the fused stencil "
                f"launches — pass fused='one_launch' or 'two_launch'")
        self.backend = target.executor          # legacy introspection
        self.vvl = target.resolve_vvl()
        self.mesh = mesh
        self.shard_axis = shard_axis
        if fused is True:
            fused = "one_launch"
        if fused not in _FUSED_MODES:
            raise ValueError(f"fused must be one of {_FUSED_MODES} (or "
                             f"True ≡ 'one_launch'), got {fused!r}")
        self.fused = fused
        self.dtype = dtype

        consts = lbp.collision_consts(dtype=np.dtype(dtype),
                                      **self.params.as_kwargs())
        kw = dict(grid_shape=self.grid_shape, mesh=mesh,
                  shard_axis=shard_axis, overlap=overlap)
        if fused:
            self.programs = {
                "collide": lbp.collide_program(consts).compile(target, **kw),
                "fused": lbp.fused_program(fused, consts).compile(target,
                                                                  **kw),
                "stream": lbp.stream_program().compile(target, **kw),
            }
        else:
            self.programs = {
                "step": lbp.unfused_step_program(consts).compile(target,
                                                                 **kw),
            }

    # -- initialisation ----------------------------------------------------

    def init_spinodal(self, seed: int = 0, noise: float = 0.05) -> LBState:
        """Symmetric quench: φ = small random noise, fluid at rest."""
        rng = np.random.default_rng(seed)
        phi0 = noise * (2.0 * rng.random(self.grid_shape) - 1.0)
        return self._equilibrium_state(phi0)

    def init_droplet(self, radius: float | None = None) -> LBState:
        """A φ=+1 droplet in a φ=-1 bath (surface-tension/Laplace tests)."""
        gs = self.grid_shape
        radius = radius or min(gs) / 4.0
        axes = [np.arange(s) - s / 2.0 + 0.5 for s in gs]
        r = np.sqrt(sum(a ** 2 for a in np.meshgrid(*axes, indexing="ij")))
        width = self.params.interface_width
        phi0 = np.tanh((radius - r) / width)
        return self._equilibrium_state(phi0)

    def _equilibrium_state(self, phi0: np.ndarray) -> LBState:
        w = WEIGHTS.reshape(NVEL, 1, 1, 1)
        f0 = (w * self.params.rho0 * np.ones_like(phi0)[None]).astype(self.dtype)
        g0 = (w * phi0[None]).astype(self.dtype)
        sharding = self._sharding()
        return LBState(jax.device_put(jnp.asarray(f0), sharding),
                       jax.device_put(jnp.asarray(g0), sharding))

    def _sharding(self):
        if self.mesh is None:
            return None
        axes = ((self.shard_axis,) if isinstance(self.shard_axis, str)
                else tuple(self.shard_axis))
        spec = P(*((None,) + axes + (None,) * (3 - len(axes))))
        return NamedSharding(self.mesh, spec)

    # -- stepping ------------------------------------------------------------

    def step(self, state: LBState, nsteps: int = 1) -> LBState:
        """``nsteps`` steps, one jitted Program step per iteration
        (python loop — bit-identical to :meth:`run`'s scan)."""
        if nsteps <= 0:
            return state
        s = {"f": state.f, "g": state.g}
        if self.fused:
            s = self.programs["collide"].step(s)
            for _ in range(nsteps - 1):
                s = self.programs["fused"].step(s)
            s = self.programs["stream"].step(s)
        else:
            for _ in range(nsteps):
                s = self.programs["step"].step(s)
        return LBState(s["f"], s["g"], state.step + nsteps)

    def run(self, state: LBState, nsteps: int, *,
            donate: bool = False) -> LBState:
        """``nsteps`` steps under one jitted ``lax.scan`` per Program.

        ``donate=True`` donates the hot-loop field buffers (ping-pong
        aliasing, no per-step reallocation) — the input state is consumed.
        """
        if nsteps <= 0:
            return state
        s = {"f": state.f, "g": state.g}
        with TraceAnnotation("tdp.sim.run"):
            if self.fused:
                s = self.programs["collide"].step(s)
                s = self.programs["fused"].run(s, nsteps - 1, donate=donate)
                s = self.programs["stream"].step(s)
            else:
                s = self.programs["step"].run(s, nsteps, donate=donate)
        return LBState(s["f"], s["g"], state.step + nsteps)

    def run_scanned(self, state: LBState, nsteps: int) -> LBState:
        """Pre-Program spelling of :meth:`run` (kept for callers; see the
        migration table in docs/targetdp_api.md)."""
        return self.run(state, nsteps)

    # -- observables ---------------------------------------------------------

    def observables(self, state: LBState) -> dict:
        """The run's statistics, read to the host one at a time; each
        read is the host span ``tdp.observables.<name>``."""
        f, g = state.f, state.g
        with TraceAnnotation("tdp.sim.observables"):
            phi = g.sum(0)
            rho = f.sum(0)
            reads = {
                "mass": lambda: float(rho.sum()),
                "phi_total": lambda: float(phi.sum()),
                "phi_min": lambda: float(phi.min()),
                "phi_max": lambda: float(phi.max()),
                "phi_var": lambda: float(phi.var()),
                "rho_min": lambda: float(rho.min()),
                "nan": lambda: bool(jnp.isnan(f).any() | jnp.isnan(g).any()),
            }
            out = {}
            for name, read in reads.items():
                with TraceAnnotation(f"tdp.observables.{name}"):
                    out[name] = read()
        return out
