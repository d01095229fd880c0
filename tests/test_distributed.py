"""Multi-device behaviour, via subprocesses with 8 fake CPU devices.

Each test launches a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the main test
process must keep seeing 1 device), runs a scenario on a (2,2,2) or (2,4)
mesh, and asserts on printed results.  Scenarios:

* sharded train step == single-device train step (GSPMD correctness),
* expert-TP MoE == local MoE; a2a MoE == expert-TP (generous capacity),
* sequence-sharded flash-decode == local decode,
* EF-int8 compressed pod psum ≈ exact psum, error feedback carries,
* LB slab-, pencil- and block-decomposed sims match the single-device
  sim under the cross-path contract (``check_fields``).
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow   # multi-device subprocess scenarios

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import check_fields
assert len(jax.devices()) == 8, jax.devices()
"""


class TestDistributed:
    def test_sharded_train_step_matches_local(self):
        run_sub(PRELUDE + """
from repro.models.config import ModelConfig, AttnConfig, repeat_program
from repro.models import params as Pm, lm
from repro.models.context import ExecContext
from repro.launch.mesh import make_test_mesh
from repro.sharding import make_plan, sharding_for_tree

cfg = ModelConfig(name="t", d_model=64, n_layers=2, vocab_size=256, d_ff=128,
    layer_program=repeat_program(("attn",), 2), attn=AttnConfig(4, 2, 16))
params, axes = Pm.init_params(cfg, jax.random.PRNGKey(0))
batch = {"tokens": jnp.tile(jnp.arange(32, dtype=jnp.int32)[None], (8, 1)) % 256,
         "labels": jnp.ones((8, 32), jnp.int32)}

l_local = lm.loss_fn(params, batch, cfg, ExecContext())[0]

mesh = make_test_mesh((2, 4), ("data", "model"))
ctx = ExecContext(mesh=mesh, batch_axes=("data",), model_axis="model")
sh = sharding_for_tree(axes, make_plan(cfg), mesh)
params_s = jax.device_put(params, sh)
bsh = NamedSharding(mesh, P("data", None))
batch_s = {k: jax.device_put(v, bsh) for k, v in batch.items()}
l_shard = jax.jit(lambda p, b: lm.loss_fn(p, b, cfg, ctx)[0])(params_s, batch_s)
np.testing.assert_allclose(float(l_local), float(l_shard), rtol=2e-5)
print("SHARDED_OK", float(l_local), float(l_shard))
""")

    def test_moe_expert_tp_and_a2a_match_local(self):
        run_sub(PRELUDE + """
from repro.models.config import ModelConfig, AttnConfig, MoEConfig, repeat_program
from repro.models import params as Pm, moe
from repro.models.context import ExecContext
from repro.launch.mesh import make_test_mesh

cfg = ModelConfig(name="m", d_model=32, n_layers=1, vocab_size=64, d_ff=64,
    layer_program=("attn_moe",), attn=AttnConfig(2, 2, 16),
    moe=MoEConfig(num_experts=8, top_k=2, d_expert=16, num_shared=1,
                  capacity_factor=4.0))
params, _ = Pm.init_params(cfg, jax.random.PRNGKey(0))
mp = jax.tree.map(lambda t: t[0], params["groups"][0][0])["mlp"]
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))

out_local = moe.moe_mlp(mp, x, cfg, ExecContext())
mesh = make_test_mesh((2, 4), ("data", "model"))
ctx = ExecContext(mesh=mesh, batch_axes=("data",), model_axis="model")
out_tp = jax.jit(lambda m_, x_: moe.moe_mlp(m_, x_, cfg, ctx))(mp, x)
np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_tp),
                           rtol=5e-4, atol=5e-5)
out_a2a = jax.jit(lambda m_, x_: moe.moe_a2a(m_, x_, cfg, ctx,
                                             capacity_factor=8.0))(mp, x)
np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_a2a),
                           rtol=5e-4, atol=5e-5)
print("MOE_OK")
""")

    def test_seq_sharded_decode_matches_local(self):
        run_sub(PRELUDE + """
from repro.models.config import AttnConfig
from repro.models import attention
from repro.models.context import ExecContext
from repro.launch.mesh import make_test_mesh

a = AttnConfig(n_heads=4, n_kv_heads=2, head_dim=16)
p = {"wq": jax.random.normal(jax.random.PRNGKey(0), (32, 64)) * .1,
     "wk": jax.random.normal(jax.random.PRNGKey(1), (32, 32)) * .1,
     "wv": jax.random.normal(jax.random.PRNGKey(2), (32, 32)) * .1,
     "wo": jax.random.normal(jax.random.PRNGKey(3), (64, 32)) * .1}
x = jax.random.normal(jax.random.PRNGKey(4), (2, 1, 32))
cache = {"k": jax.random.normal(jax.random.PRNGKey(5), (2, 2, 64, 16)),
         "v": jax.random.normal(jax.random.PRNGKey(6), (2, 2, 64, 16))}
length = 40
out_local, _ = attention.decode_attention(p, x, a, ExecContext(),
                                          jax.tree.map(jnp.copy, cache), length)
mesh = make_test_mesh((2, 4), ("data", "model"))
ctx = ExecContext(mesh=mesh, batch_axes=("data",), model_axis="model",
                  seq_shard_decode=True)
out_s, _ = jax.jit(lambda p_, x_, c_: attention.decode_attention(
    p_, x_, a, ctx, c_, length))(p, x, cache)
np.testing.assert_allclose(np.asarray(out_local), np.asarray(out_s),
                           rtol=2e-4, atol=2e-5)
print("FLASH_DECODE_OK")
""")

    def test_compressed_pod_psum(self):
        run_sub(PRELUDE + """
from repro.optim.compress import compressed_psum_mean, compress_init
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))

g = {"w": jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))}
e = {"w": jnp.zeros((64, 32))}

def body(g_l, e_l):
    red, new_e = compressed_psum_mean({"w": g_l["w"]}, {"w": e_l["w"]}, "pod")
    return red["w"], new_e["w"]

fn = jax.shard_map(body, mesh=mesh,
                   in_specs=({"w": P("pod")}, {"w": P()}),
                   out_specs=(P(), P()), check_vma=False)
red, err = jax.jit(fn)(g, e)
exact = g["w"].mean(0)
rel = float(jnp.abs(red - exact).max() / jnp.abs(exact).max())
assert rel < 0.02, rel                        # int8 quant error bounded
# error feedback buffer carries the residual
assert float(jnp.abs(err).max()) > 0
# second round with EF: cumulative mean converges closer
print("COMPRESS_OK", rel)
""")

    def test_lb_sharded_sim_matches_local(self):
        run_sub(PRELUDE + """
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((8,), ("data",))
s_loc = BinaryFluidSim((16, 8, 8))
s_sh = BinaryFluidSim((16, 8, 8), mesh=mesh, shard_axis="data")
st0 = s_loc.init_spinodal(seed=1)
st1 = s_sh.init_spinodal(seed=1)
a = s_loc.step(st0, 5)
b = s_sh.step(st1, 5)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})
print("LB_HALO_OK")
""")

    def test_lb_fused_sharded_sim_matches_local(self):
        """Fused stream+collide under slab decomposition: the 2-plane
        ppermute halo exchange feeds the radius-2 composed stencil."""
        run_sub(PRELUDE + """
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((8,), ("data",))
s_loc = BinaryFluidSim((16, 8, 8))
s_sh = BinaryFluidSim((16, 8, 8), mesh=mesh, shard_axis="data", fused=True)
st0 = s_loc.init_spinodal(seed=1)
st1 = s_sh.init_spinodal(seed=1)
a = s_loc.step(st0, 5)
b = s_sh.step(st1, 5)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})
print("LB_FUSED_HALO_OK")
""")

    def test_lb_windowed_sharded_sim_matches_local(self):
        """Fused step on the gather-free pallas_windowed executor under
        slab decomposition: the same 2-plane ppermute exchange feeds the
        halo_extend prologue (ghost planes trimmed to each stencil's
        radius, y/z wrap-padded) instead of the offset gather — the
        trajectory still matches the single-device xla sim."""
        run_sub(PRELUDE + """
from repro import tdp
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((4,), ("data",))
wt = tdp.Target("pallas_windowed", interpret=True)
s_loc = BinaryFluidSim((16, 8, 8))
s_sh = BinaryFluidSim((16, 8, 8), mesh=mesh, shard_axis="data", fused=True,
                      target=wt)
st0 = s_loc.init_spinodal(seed=1)
st1 = s_sh.init_spinodal(seed=1)
a = s_loc.step(st0, 5)
b = s_sh.step(st1, 5)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})
print("LB_WINDOWED_HALO_OK")
""")

    def test_lb_two_launch_sharded_sim_matches_local(self):
        """Two-launch fused step under slab decomposition: launch A
        recomputes the streamed-φ ghost ring locally from the width-2
        exchange — no extra communication for the intermediate."""
        run_sub(PRELUDE + """
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((8,), ("data",))
s_loc = BinaryFluidSim((16, 8, 8))
s_sh = BinaryFluidSim((16, 8, 8), mesh=mesh, shard_axis="data",
                      fused="two_launch")
st0 = s_loc.init_spinodal(seed=1)
st1 = s_sh.init_spinodal(seed=1)
a = s_loc.step(st0, 5)
b = s_sh.step(st1, 5)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})
print("LB_TWO_LAUNCH_HALO_OK")
""")

    def test_lb_program_sharded_4way_matches_local(self):
        """The tdp.Program sharded step: one ghost-exchange round per
        step at the back-propagated widths ({f: 1, g: 2} for the
        two-launch graph — f travels *one* plane, not the old blanket
        two), matching the single-device trajectory on a 4-way slab
        decomposition under the cross-path contract."""
        run_sub(PRELUDE + """
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((4,), ("data",))
s_loc = BinaryFluidSim((16, 8, 8), fused="two_launch")
s_sh = BinaryFluidSim((16, 8, 8), mesh=mesh, shard_axis="data",
                      fused="two_launch")
assert s_sh.programs["fused"].halo_schedule == {"f": 1, "g": 2}, \\
    s_sh.programs["fused"].halo_schedule
# the collide prologue has no stream stage: f needs no exchange at all
assert s_sh.programs["collide"].halo_schedule == {"f": 0, "g": 1}
assert s_sh.programs["stream"].halo_schedule == {"f": 1, "g": 1}
st0 = s_loc.init_spinodal(seed=1)
st1 = s_sh.init_spinodal(seed=1)
a = s_loc.step(st0, 5)
b = s_sh.step(st1, 5)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})
c = s_sh.run(st1, 5)
np.testing.assert_array_equal(np.asarray(b.f), np.asarray(c.f))
np.testing.assert_array_equal(np.asarray(b.g), np.asarray(c.g))

# maximal decomposition: a 1-plane slab under the width-2 g schedule
# (the exchange hops two ranks) still matches the local trajectory
t_loc = BinaryFluidSim((4, 8, 8))
t_sh = BinaryFluidSim((4, 8, 8), mesh=mesh, shard_axis="data")
u0 = t_loc.init_spinodal(seed=2)
u1 = t_sh.init_spinodal(seed=2)
ua = t_loc.step(u0, 4)
ub = t_sh.step(u1, 4)
check_fields({"f": ua.f, "g": ua.g}, {"f": ub.f, "g": ub.g})
print("LB_PROGRAM_4WAY_OK")
""")

    def test_lb_pencil_2x2_matches_local(self):
        """The tentpole pin: a 2-D pencil decomposition (mesh axes
        (px, py) sharding grid dims 0 and 1) matches the single-device
        trajectory over 10 steps at 16³ under the cross-path contract,
        with one exchange round per field per sharded dim — the per-dim
        widths mirror the slab schedule and the lowered HLO carries
        exactly the analytic ppermute count."""
        run_sub(PRELUDE + """
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 2), ("px", "py"))
s_loc = BinaryFluidSim((16, 16, 16), fused="two_launch")
s_sh = BinaryFluidSim((16, 16, 16), mesh=mesh, shard_axis=("px", "py"),
                      fused="two_launch")
exe = s_sh.programs["fused"]
assert exe.exchange_schedule == {"f": {0: 1, 1: 1}, "g": {0: 2, 1: 2}}, \\
    exe.exchange_schedule
assert exe.halo_schedule == {"f": 1, "g": 2}     # legacy dim-0 view
assert s_sh.programs["collide"].exchange_schedule == \\
    {"f": {}, "g": {0: 1, 1: 1}}
cs = exe.comm_stats()
assert cs["decomposition"] == "pencil" and cs["mesh_axis_sizes"] == (2, 2)
assert cs["local_shape"] == (8, 8, 16)
# one round per field per sharded dim, single-hop: 2 ppermutes each
assert cs["ppermutes_per_step"] == 8, cs
assert cs["exchanged_bytes_per_step"] > 0
st0 = s_loc.init_spinodal(seed=3)
st1 = s_sh.init_spinodal(seed=3)
a = s_loc.step(st0, 10)
b = s_sh.step(st1, 10)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})
# the same sharded program, stepped and scanned: bitwise
c = s_sh.run(s_sh.init_spinodal(seed=3), 10)
np.testing.assert_array_equal(np.asarray(b.f), np.asarray(c.f))
np.testing.assert_array_equal(np.asarray(b.g), np.asarray(c.g))
# the per-step exchange count matches the schedule: count the
# collective permutes in the lowered step HLO
txt = jax.jit(exe._core).lower(*exe._as_tuple(
    {"f": st1.f, "g": st1.g})).as_text()
n_cp = txt.count("collective-permute") + txt.count("collective_permute")
assert n_cp == cs["ppermutes_per_step"], (n_cp, cs["ppermutes_per_step"])
print("LB_PENCIL_2X2_OK")
""")

    def test_lb_pencil_overlap_schedule(self):
        """overlap=True splits every stage into interior + boundary
        regions (interior launched off the raw local arrays, no ppermute
        dependency).  The split is data-exact but region-shaped XLA
        codegen reassociates at <=1 ULP: the pin is the cross-path
        contract plus the schedule introspection."""
        run_sub(PRELUDE + """
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh((2, 2), ("px", "py"))
s_loc = BinaryFluidSim((16, 16, 16), fused="two_launch")
s_ov = BinaryFluidSim((16, 16, 16), mesh=mesh, shard_axis=("px", "py"),
                      fused="two_launch", overlap=True)
exe = s_ov.programs["fused"]
assert exe.overlap is True
cs = exe.comm_stats()
assert cs["overlap"] is True
# interior (8-2*2)^2*16 of 8*8*16 local sites
assert abs(cs["interior_fraction"] - 16.0 / 64.0) < 1e-12
a = s_loc.step(s_loc.init_spinodal(seed=3), 10)
b = s_ov.step(s_ov.init_spinodal(seed=3), 10)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})
# default compile stays unsplit
assert BinaryFluidSim((16, 16, 16), mesh=mesh, shard_axis=("px", "py"),
                      fused="two_launch").programs["fused"].overlap is False
print("LB_PENCIL_OVERLAP_OK")
""")

    def test_lb_block_and_thin_pencil(self):
        """Degenerate 3-D block decomposition and the multi-hop thin
        pencil (1-plane local extent under a width-2 schedule reads from
        ranks ±2 along that mesh axis) both match the local trajectory."""
        run_sub(PRELUDE + """
from repro.lb.sim import BinaryFluidSim
from repro.launch.mesh import make_test_mesh
# 2x2x2 block at 16^3
mb = make_test_mesh((2, 2, 2), ("bx", "by", "bz"))
s_loc = BinaryFluidSim((16, 16, 16), fused="two_launch")
s_bl = BinaryFluidSim((16, 16, 16), mesh=mb,
                      shard_axis=("bx", "by", "bz"), fused="two_launch")
assert s_bl.programs["fused"].comm_stats()["decomposition"] == "block"
a = s_loc.step(s_loc.init_spinodal(seed=3), 5)
b = s_bl.step(s_bl.init_spinodal(seed=3), 5)
check_fields({"f": a.f, "g": a.g}, {"f": b.f, "g": b.g})

# thin pencil: mesh (2,4) on (8,4,8) -> local (4,1,8); g's width-2
# exchange in dim 1 needs 2 hops per side (4 ppermutes)
mt = make_test_mesh((2, 4), ("tx", "ty"))
t_loc = BinaryFluidSim((8, 4, 8), fused="two_launch")
t_sh = BinaryFluidSim((8, 4, 8), mesh=mt, shard_axis=("tx", "ty"),
                      fused="two_launch")
cs = t_sh.programs["fused"].comm_stats()
assert cs["per_field"]["g"]["ppermutes"] == 6, cs   # 2 (dim0) + 4 (dim1)
ua = t_loc.step(t_loc.init_spinodal(seed=1), 5)
ub = t_sh.step(t_sh.init_spinodal(seed=1), 5)
check_fields({"f": ua.f, "g": ua.g}, {"f": ub.f, "g": ub.g})
print("LB_BLOCK_THIN_OK")
""")

    def test_trainer_on_mesh_with_compression(self):
        run_sub(PRELUDE + """
import tempfile
from repro.models.config import ModelConfig, AttnConfig, repeat_program
from repro.data import SyntheticConfig
from repro.optim import AdamWConfig
from repro.runtime import Trainer, TrainerConfig, TrainHParams
from repro.launch.mesh import make_test_mesh

cfg = ModelConfig(name="t", d_model=32, n_layers=2, vocab_size=64, d_ff=64,
    layer_program=repeat_program(("attn",), 2), attn=AttnConfig(2, 2, 16))
mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
with tempfile.TemporaryDirectory() as d:
    # fsdp=False: FSDP + partial-manual pod shard_map trips an XLA
    # partitioner CHECK (documented in runtime/trainer.py)
    tr = Trainer(cfg, mesh, SyntheticConfig(64, 16, 8),
                 AdamWConfig(),
                 TrainHParams(grad_accum=2, warmup_steps=2, total_steps=20,
                              compress_pod=True),
                 TrainerConfig(ckpt_dir=d, ckpt_every=100, log_every=100,
                               fsdp=False, log=lambda *_: None))
    tr.train_steps(6)
    import math
    losses = [h for h in tr.metrics_history]
    print("TRAINER_MESH_OK", tr.step)
    assert tr.step == 6
""", timeout=900)
