"""Launch layer: cell builder, dry-run record pipeline, elastic restore
across mesh shapes (subprocess-isolated where device counts differ)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


class TestCellBuilder:
    def test_dryrun_cell_end_to_end(self, tmp_path):
        """One real dry-run cell on the production mesh: lower, compile,
        analyse, JSON record — the full deliverable-(e) pipeline."""
        out = run_sub(f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
from repro.launch.dryrun import run_cell
from repro.launch.cells import Variant
rec = run_cell("granite_moe_1b_a400m", "decode_32k", "single",
               Variant(), {str(tmp_path)!r}, force=True)
assert rec["status"] == "ok", rec.get("error")
assert rec["n_devices"] == 256
ha = rec["hlo_analysis"]
assert ha["flops"] > 0 and ha["traffic_bytes"] > 0
assert rec["memory_analysis"]["temp_size_in_bytes"] > 0
print("CELL_OK", round(ha["flops"]/1e9, 2))
""", devices=512)
        assert "CELL_OK" in out
        files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(files) == 1
        rec = json.load(open(os.path.join(tmp_path, files[0])))
        assert rec["arch"] == "granite_moe_1b_a400m"

    def test_variant_overrides_reach_context(self):
        from repro.launch.cells import Variant
        v = Variant(name="x", grad_accum=4, seq_over_data=True)
        assert v.with_(grad_accum=8).grad_accum == 8
        assert v.name == "x" and v.seq_over_data

    def test_mesh_factories(self):
        """Factories are pure descriptions until called (no import-time
        device access) — validated by signature + the dryrun itself."""
        import inspect
        from repro.launch import mesh
        sig = inspect.signature(mesh.make_production_mesh)
        assert "multi_pod" in sig.parameters


class TestElasticRestore:
    def test_checkpoint_crosses_mesh_shapes(self, tmp_path):
        """Train on a (2,4) mesh, checkpoint, restore onto (8,1) and
        (1,1): the elastic-scaling story end to end, loss continues."""
        run_sub(f"""
import jax, jax.numpy as jnp, numpy as np
from repro.models.config import ModelConfig, AttnConfig, repeat_program
from repro.data import SyntheticConfig
from repro.optim import AdamWConfig
from repro.runtime import Trainer, TrainerConfig, TrainHParams
from repro.launch.mesh import make_test_mesh

cfg = ModelConfig(name="t", d_model=32, n_layers=2, vocab_size=64, d_ff=64,
    layer_program=repeat_program(("attn",), 2), attn=AttnConfig(2, 2, 16))
data = SyntheticConfig(64, 16, 8)
hp = TrainHParams(warmup_steps=2, total_steps=50)

mesh_a = make_test_mesh((2, 4), ("data", "model"))
tc = TrainerConfig(ckpt_dir={str(tmp_path)!r}, ckpt_every=5,
                   log_every=100, log=lambda *_: None)
tr = Trainer(cfg, mesh_a, data, AdamWConfig(), hp, tc)
tr.train_steps(5)
tr.ckpt.wait()
ref = np.asarray(jax.device_get(jax.tree.leaves(tr.params)[0]))

for shape in ((8, 1), (1, 1)):
    mesh_b = make_test_mesh(shape, ("data", "model"))
    tr2 = Trainer(cfg, mesh_b, data, AdamWConfig(), hp, tc)
    assert tr2.restore_latest() and tr2.step == 5
    got = np.asarray(jax.device_get(jax.tree.leaves(tr2.params)[0]))
    np.testing.assert_array_equal(got, ref)     # bit-exact across meshes
    tr2.train_steps(2)                          # and it keeps training
    assert tr2.step == 7
print("ELASTIC_OK")
""")


def test_compile_cache_dir_follows_env_or_checkout(monkeypatch):
    """``enable_compile_cache`` leaves JAX's own ``JAX_COMPILATION_CACHE_DIR``
    alone when it is set, and otherwise points the cache at one fixed
    directory inside the checkout."""
    from pathlib import Path

    from repro.launch import cache

    calls = []
    monkeypatch.setattr(cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cache.enable_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    assert cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
