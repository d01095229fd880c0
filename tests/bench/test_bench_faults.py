"""A run with its timed path broken underneath comes out not correct.

Each case drives the whole of a run except the look for a chip
(`harness.run_cell`), on the CPU at a small size, with one fault planted
in the system under test: a call that returns its state unchanged, half
of the lattice left un-updated, a population altered where the step
produces it, and a statistic altered where the host receives it.  The
same run without a fault is correct.  The exchange left out between
chips is `test_bench_pencil.py`'s.
"""
import time

import jax
import jax.numpy as jnp
import pytest

from bench_helpers import small_config

from bench.lib import harness, lattice


@pytest.fixture(scope="module")
def built():
    """One compiled system for every case, so the CPU compiles it once."""
    cache = {}
    real = lattice.build

    def build(cfg, devices):
        if cfg["name"] not in cache:
            cache[cfg["name"]] = real(cfg, devices)
        return cache[cfg["name"]]

    return build


def _unchanged(sim):
    sim.run = lambda state, n, donate=False: state


def _half_lattice(sim):
    run = sim.run

    def half(state, n, donate=False):
        f0, g0 = lattice.copy_fields(state.f, state.g)
        out = run(state, n, donate=donate)
        x = out.f.shape[1] // 2
        out.f = jnp.concatenate([out.f[:, :x], f0[:, x:]], axis=1)
        out.g = jnp.concatenate([out.g[:, :x], g0[:, x:]], axis=1)
        return out
    sim.run = half


def _site_altered(sim):
    run = sim.run

    def altered(state, n, donate=False):
        out = run(state, n, donate=donate)
        out.f = out.f.at[3, 1, 2, 3].add(1e-3)
        return out
    sim.run = altered


def _statistic_altered(sim):
    obs = sim.observables

    def altered(state):
        o = obs(state)
        o["phi_var"] *= 1.0 + 1e-3
        return o
    sim.observables = altered


FAULTS = {"unchanged_state": (_unchanged, "max_abs_df"),
          "half_lattice": (_half_lattice, "max_abs_dg"),
          "site_altered": (_site_altered, "max_abs_df"),
          "statistic_altered": (_statistic_altered, "obs_gap")}


def _run(root, built, monkeypatch, fault=None):
    def build(cfg, devices):
        sim = built(cfg, devices)
        for attr in ("run", "observables"):
            monkeypatch.setattr(sim, attr, getattr(type(sim), attr).__get__(sim))
        if fault is not None:
            fault(sim)
        return sim

    monkeypatch.setattr(lattice, "build", build)
    return harness.run_cell(root.path, "small.stats10", seed=2 ** 31 + 5, seconds=0.5,
                            trace=False, t_start=time.perf_counter(), log=lambda line: None)


@pytest.fixture
def root(scratch_root):
    scratch_root.add("configs", "small", small_config("small", grid=[16, 16, 16],
                                                      executor="xla"))
    scratch_root.add("traffic", "stats10", {"name": "stats10", "steps_per_call": 10,
                                            "donate": True})
    scratch_root.add_cell({"name": "small.stats10", "config": "small", "traffic": "stats10",
                           "chips": 1, "why": "test"})
    return scratch_root


def test_sound_run_is_correct(root, built, monkeypatch):
    res = _run(root, built, monkeypatch)
    assert res["correct"], res["check"]
    assert res["device"]["platform"] == jax.devices()[0].platform


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_fault_is_not_correct(root, built, monkeypatch, name):
    fault, number = FAULTS[name]
    res = _run(root, built, monkeypatch, fault)
    assert not res["correct"]
    c = res["check"][number]
    assert not c["value"] <= c["limit"], res["check"]
