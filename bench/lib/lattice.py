"""The system under test, built from a configuration file: the binary-fluid
simulation of `repro.lb.sim`, and its initial state made on the device
from the seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed) % 2 ** 64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def build(cfg: dict, devices):
    """The configuration's `BinaryFluidSim` on `devices` (one, or a mesh)."""
    from repro import tdp
    from repro.lb.params import LBParams
    from repro.lb.sim import BinaryFluidSim

    mesh, shard_axis = None, "data"
    if cfg["mesh"] is not None:
        shape, axes = tuple(cfg["mesh"]["shape"]), tuple(cfg["mesh"]["axes"])
        n = int(np.prod(shape))
        if len(devices) < n:
            raise RuntimeError(f"mesh {shape} needs {n} devices, have {len(devices)}")
        mesh = Mesh(np.array(devices[:n]).reshape(shape), axes)
        shard_axis = axes
    if cfg["dtype"] != "float32":
        raise ValueError(f"dtype {cfg['dtype']!r}: only float32 configurations exist")
    return BinaryFluidSim(tuple(cfg["grid"]), params=LBParams(**cfg["physics"]),
                          target=tdp.Target(cfg["executor"]), fused=cfg["fused"],
                          mesh=mesh, shard_axis=shard_axis)


def state_sharding(cfg: dict, sim):
    if sim.mesh is None:
        return jax.sharding.SingleDeviceSharding(jax.devices()[0])
    spec = PartitionSpec(None, *cfg["mesh"]["axes"], None)
    return NamedSharding(sim.mesh, spec)


def init_state(cfg: dict, sim, seed: int, ref):
    """The symmetric quench of `init_spinodal`, made on the device in one
    jitted call: f = w rho0, g = w phi0, phi0 uniform in +-noise, with the
    weights w of the reference module `ref`."""
    from repro.lb.sim import LBState

    grid, noise = tuple(cfg["grid"]), float(cfg["init"]["noise"])
    rho0 = float(cfg["physics"]["rho0"])
    w = jnp.asarray(ref.WEIGHTS, jnp.float32).reshape(ref.NVEL, 1, 1, 1)
    sh = state_sharding(cfg, sim)

    @jax.jit
    def make(key):
        phi0 = noise * (2.0 * jax.random.uniform(key, grid, jnp.float32) - 1.0)
        f = jax.lax.with_sharding_constraint(w * rho0 * jnp.ones(grid, jnp.float32), sh)
        g = jax.lax.with_sharding_constraint(w * phi0, sh)
        return f, g

    f, g = make(seed_key(seed))
    return LBState(f, g)


def kernel_steps(cfg: dict, calls: int, steps_per_call: int) -> int:
    """Steps of `calls` calls that the fused kernel makes: a fused call runs
    its first step's collide and last step's stream as XLA programs of
    their own, and the kernel for the other steps."""
    return calls * (steps_per_call - 1) if cfg["fused"] else 0


@jax.jit
def copy_fields(f, g):
    """Device copies of the fields, which outlive a donating call."""
    return jnp.copy(f), jnp.copy(g)


def info_lines(cfg: dict, sim) -> list[str]:
    """Information only, printed before the window: the program's own
    estimates, which no metric reads."""
    lines = []
    for name, prog in sim.programs.items():
        lines.append(f"[bench] info program={name} hbm_bytes_estimate_per_step="
                     f"{prog.plan().hbm_bytes_estimate()}")
    if sim.mesh is not None:
        cs = sim.programs["fused"].comm_stats()
        lines.append(f"[bench] info comm_stats decomposition={cs['decomposition']} "
                     f"local_shape={cs['local_shape']} "
                     f"ppermutes_per_step={cs['ppermutes_per_step']} "
                     f"exchanged_bytes_per_step={cs['exchanged_bytes_per_step']}")
    return lines
