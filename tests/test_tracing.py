"""The program's own trace names: device scopes, module names and host
spans.

Scopes (`jax.named_scope`) reach the lowered HLO as each op's location,
and from there the profiler's `tf_op` path; the jitted step and scan carry
the program's name; `CompiledProgram` and `BinaryFluidSim` open host spans
(`jax.profiler.TraceAnnotation`) around every dispatch and statistic.
"""
import glob
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from repro import tdp
from repro.lb.sim import BinaryFluidSim

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
GRID = (16, 16, 16)
STATS = ("mass", "phi_total", "phi_min", "phi_max", "phi_var", "rho_min", "nan")


def scopes_in(hlo_text: str) -> set:
    """The `tdp.*` scopes on the ops' locations of a lowered module."""
    return {c for loc in re.findall(r'loc\("([^"]*)"', hlo_text)
            for c in loc.split("/") if c.startswith("tdp.")}


def module_name(hlo_text: str) -> str:
    return re.search(r"module @(\w+)", hlo_text).group(1)


def host_spans(trace_dir: str) -> list:
    """`[(name, start_ns, end_ns)]` of the `tdp.*` host events of the one
    profile written under `trace_dir`."""
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for plane in pd.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events if e.name.startswith("tdp."))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A fused one_launch simulation on the xla executor (gathered
    operands), run from its first dispatch under the profiler: its state
    and the host spans of the trace."""
    sim = BinaryFluidSim(GRID, fused="one_launch")
    state = sim.init_spinodal(seed=2)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        state = sim.run(state, 3)                         # first calls: compile spans
        obs = sim.observables(state)
        state = sim.run(state, 3)
        state = sim.run(state, 4)                         # a new nsteps compiles once more
        jax.block_until_ready(state.f)
    assert set(obs) == set(STATS)
    return sim, state, host_spans(trace_dir)


def test_windowed_fused_step_carries_stage_and_prologue_scopes():
    sim = BinaryFluidSim(GRID, target=tdp.Target("pallas_windowed_interpret"),
                         fused="one_launch")
    state = sim.init_spinodal(seed=1)
    exe = sim.programs["fused"]
    text = exe._jit_step.lower(*exe._as_tuple({"f": state.f, "g": state.g})).as_text(
        debug_info=True)
    assert module_name(text) == "jit_tdp_lb_fused_one_step"
    assert {"tdp.stage.fused", "tdp.halo_extend", "tdp.operands", "tdp.outputs"} <= scopes_in(text)
    assert "tdp.exchange" not in scopes_in(text)       # one chip: nothing to exchange
    assert "tdp_windowed_fused_site_kernel_p1_soa" in text   # the kernel keeps its name


def test_gathered_launch_and_scan_carry_their_names(traced):
    sim, state, _ = traced
    exe = sim.programs["fused"]
    args = exe._as_tuple({"f": state.f, "g": state.g})
    step = exe._jit_step.lower(*args).as_text(debug_info=True)
    assert {"tdp.stage.fused", "tdp.gather"} <= scopes_in(step)
    run = exe._run_cache[(3, False)]
    text = run.lower(args).as_text(debug_info=True)
    assert module_name(text) == "jit_tdp_lb_fused_one_run"
    assert "tdp.stage.fused" in scopes_in(text)
    for name, prog in sim.programs.items():
        assert module_name(prog._jit_step.lower(*args).as_text()) == \
            f"jit_tdp_{prog.program.name}_step"


PENCIL = """
import json, re, sys
sys.path.insert(0, {src!r})
import jax
from repro import tdp
from repro.launch.mesh import make_test_mesh
from repro.lb.sim import BinaryFluidSim
assert len(jax.devices()) == 2, jax.devices()
mesh = make_test_mesh((2, 1), ("px", "py"))
sim = BinaryFluidSim((16, 16, 16), target=tdp.Target("pallas_windowed_interpret"),
                     mesh=mesh, shard_axis=("px", "py"), fused="two_launch")
state = sim.init_spinodal(seed=1)
out = {{}}
for name, prog in sim.programs.items():
    text = prog._jit_step.lower(*prog._as_tuple({{"f": state.f, "g": state.g}})).as_text(
        debug_info=True)
    out[name] = {{"module": re.search(r"module @(\\w+)", text).group(1),
                 "scopes": sorted({{c for loc in re.findall(r'loc\\("([^"]*)"', text)
                                   for c in loc.split("/") if c.startswith("tdp.")}})}}
print(json.dumps(out))
"""


def test_pencil_step_carries_exchange_scopes():
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", PENCIL.format(src=SRC)], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    fused = got["fused"]
    assert fused["module"] == "jit_tdp_lb_fused_two_step"
    assert {"tdp.stage.phi_stream", "tdp.stage.fused_two", "tdp.halo_extend", "tdp.exchange",
            "tdp.operands", "tdp.outputs"} <= set(fused["scopes"])
    assert got["collide"]["module"] == "jit_tdp_lb_collide_step"
    assert {"tdp.stage.moments", "tdp.stage.gradients", "tdp.stage.collide",
            "tdp.exchange"} <= set(got["collide"]["scopes"])


def test_trim_of_a_field_that_keeps_its_ghosts():
    """A field that no stage writes leaves the step with the ghost layers it
    came in with, and is trimmed to the interior under `tdp.trim`."""
    from repro.lb.stencil import GRAD6_SPEC

    prog = tdp.program("grad", [tdp.stage(GRAD6_SPEC, reads="phi", writes=("grad", "lap"),
                                          name="grad")],
                       fields=("phi", "grad", "lap"))
    ext = jax.ShapeDtypeStruct((1, 10, 10, 10), jax.numpy.float32)

    def step(phi, grad, lap):
        return prog.execute("xla", {"phi": phi, "grad": grad, "lap": lap},
                            grid_shape=(8, 8, 8), halo=1)
    text = jax.jit(step).lower(ext, ext.update(shape=(3, 10, 10, 10)), ext).as_text(
        debug_info=True)
    assert {"tdp.stage.grad", "tdp.operands", "tdp.outputs", "tdp.gather", "tdp.trim"} <= \
        scopes_in(text)


def test_host_spans_of_a_traced_run(traced):
    _, _, spans = traced
    names = [n for n, _, _ in spans]
    count = {n: names.count(n) for n in set(names)}
    assert count["tdp.sim.run"] == 3 and count["tdp.sim.observables"] == 1
    assert count["tdp.lb_collide.compile"] == 1 and count["tdp.lb_collide.step"] == 2
    assert count["tdp.lb_stream.compile"] == 1 and count["tdp.lb_stream.step"] == 2
    assert count["tdp.lb_fused_one.compile"] == 2 and count["tdp.lb_fused_one.run"] == 1
    (o0, o1), = [(s, e) for n, s, e in spans if n == "tdp.sim.observables"]
    for stat in STATS:
        (s, e), = [(s, e) for n, s, e in spans if n == f"tdp.observables.{stat}"]
        assert o0 <= s and e <= o1, stat
    for n, s, e in spans:                                # every dispatch inside a run
        if n.startswith("tdp.lb_"):
            assert any(r0 <= s and e <= r1 for m, r0, r1 in spans if m == "tdp.sim.run"), n


def test_a_new_input_signature_shows_as_a_compile(tmp_path):
    """jit compiles again for arrays of another dtype; so does the span."""
    sim = BinaryFluidSim(GRID, fused="one_launch")
    exe = sim.programs["collide"]
    state = sim.init_spinodal(seed=3)
    f32 = {"f": state.f, "g": state.g}
    bf16 = {k: v.astype(jax.numpy.bfloat16) for k, v in f32.items()}
    with jax.profiler.trace(str(tmp_path)):
        for s in (f32, f32, bf16, bf16):
            jax.block_until_ready(exe.step(s)["f"])
    spans = sorted(host_spans(str(tmp_path)), key=lambda span: span[1])
    assert [n for n, _, _ in spans] == ["tdp.lb_collide.compile", "tdp.lb_collide.step"] * 2
