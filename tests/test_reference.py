"""The LB paths against the benchmark's plain float32 reference.

``bench/references/d3q19_binary_fluid.py`` states the binary-fluid step
from scratch, importing nothing of the program.  Every LB path the CPU
runs steps a 16³ spinodal quench ten times and is held to it under the
cross-path contract (``tdp.check_fields``); a stream that shifts one
population by one site more than it should must fail that contract.
"""
import os
import sys

import jax.numpy as jnp
import pytest

from repro import tdp
from repro.lb.params import LBParams
from repro.lb.sim import BinaryFluidSim

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from bench.references import d3q19_binary_fluid as ref  # noqa: E402

GRID = (16, 16, 16)
STEPS = 10
PARAMS = LBParams(A=0.125, B=0.125, kappa=0.02)
WINDOWED = tdp.Target("pallas_windowed", interpret=True)

PATHS = {
    "unfused-xla": dict(fused=False),
    "one_launch-xla": dict(fused="one_launch"),
    "two_launch-xla": dict(fused="two_launch"),
    "one_launch-pallas_windowed_interpret": dict(fused="one_launch",
                                                 target=WINDOWED),
    "two_launch-pallas_windowed_interpret": dict(fused="two_launch",
                                                 target=WINDOWED),
}


@pytest.fixture(scope="module")
def start_and_reference():
    st0 = BinaryFluidSim(GRID, params=PARAMS).init_spinodal(seed=3,
                                                            noise=0.05)
    f, g = ref.run(st0.f, st0.g, STEPS, PARAMS.as_kwargs())
    return st0, {"f": f, "g": g}


@pytest.mark.parametrize("path", list(PATHS))
def test_matches_plain_reference(start_and_reference, path):
    st0, want = start_and_reference
    sim = BinaryFluidSim(GRID, params=PARAMS, **PATHS[path])
    got = sim.run(st0, STEPS)
    tdp.check_fields(want, {"f": got.f, "g": got.g})


def test_a_stream_shifted_by_one_site_fails(start_and_reference):
    """Population 1 (c = +x) streamed two sites a step instead of one:
    a real defect, which the contract's tolerance must see."""
    st0, want = start_and_reference
    sim = BinaryFluidSim(GRID, params=PARAMS)
    st = st0
    for _ in range(STEPS):
        st = sim.step(st, 1)
        st.f = st.f.at[1].set(jnp.roll(st.f[1], 1, axis=0))
    with pytest.raises(AssertionError, match="field 'f'"):
        tdp.check_fields(want, {"f": st.f, "g": st.g})
