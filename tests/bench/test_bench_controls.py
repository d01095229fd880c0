"""The controls of the comparison: the plain reference, one precision tier
below the configuration's, put in the program's place.

On the chip the controls were read at the cells' own sizes
(`bench/calibrate.py`, PERF.md §2); here they run through the whole of a
run but the look for a chip, at a size the CPU holds (16³, 100-step
calls), where the cells' limits, set from 128³ chip readings, do not
all carry over.  So the test holds each control to what does: the
`bf16_state` control fails every limited number, and the `high` control
reads its largest gap in g at three times the program's or more.
"""
import time

import pytest

from bench_helpers import small_config

from bench.lib import harness

SEEDS = (2 ** 31 + 11, 5)


@pytest.fixture
def root(scratch_root):
    scratch_root.add("configs", "small", small_config("small", grid=[16, 16, 16],
                                                      executor="xla"))
    scratch_root.add_cell({"name": "small.stats100", "config": "small",
                           "traffic": "stats100", "chips": 1, "why": "test"})
    return scratch_root


def _run(root, seed, precision=None):
    """A run of the program, or of the reference at `precision` in its place
    (`harness.control_build`, as `bench/calibrate.py` runs the controls)."""
    build = None if precision is None else harness.control_build(precision)
    return harness.run_cell(root.path, "small.stats100", seed=seed, seconds=0.0,
                            trace=False, t_start=time.perf_counter(), log=lambda line: None,
                            build=build)


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_in_the_programs_place_agrees_exactly(root, seed):
    c = _run(root, seed, "float32")["check"]
    assert c["max_abs_df"]["value"] == 0 and c["max_abs_dg"]["value"] == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_bf16_state_control_fails_every_limit(root, seed):
    res = _run(root, seed, "bf16_state")
    assert not res["correct"]
    for name in ("max_abs_df", "max_abs_dg", "obs_gap"):
        c = res["check"][name]
        assert c["value"] > c["limit"], (name, res["check"])


@pytest.mark.parametrize("seed", SEEDS)
def test_high_control_reads_well_above_the_program(root, seed):
    program = _run(root, seed)["check"]["max_abs_dg"]["value"]
    control = _run(root, seed, "high")["check"]["max_abs_dg"]["value"]
    assert control >= 3 * program > 0, (control, program)
