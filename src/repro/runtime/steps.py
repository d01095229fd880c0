"""train_step / serve_step builders.

``build_train_step`` assembles, from plain pieces, the jit-able function
``(params, opt_state, batch[, ef]) -> (params, opt_state, metrics[, ef])``:

* **microbatching / gradient accumulation** — the global batch is cut into
  ``grad_accum`` microbatches scanned sequentially; gradients accumulate in
  fp32.  Under GSPMD each microbatch's DP psum overlaps the next
  microbatch's compute (the scheduler interleaves the scan body's collective
  with the following iteration — the standard accumulate-overlap trick).
* **remat** — ``ctx.remat="block"`` checkpoints each layer-program unit.
* **cross-pod gradient compression** — optional: gradients are computed
  *pod-locally* under a ``shard_map`` manual over every mesh axis, then
  EF-int8 reduced over the pod (DCN) axis (:mod:`repro.optim.compress`).

``build_serve_steps`` returns (prefill_fn, decode_fn) with KV-cache
handling, greedy/temperature sampling, and flash-decoding sequence-sharded
caches when ``ctx.seq_shard_decode``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import lm
from repro.models.config import ModelConfig
from repro.models.context import ExecContext
from repro.optim import AdamWConfig, adamw_update, compressed_psum_mean
from repro.optim.schedule import warmup_cosine


@dataclass(frozen=True)
class TrainHParams:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    grad_accum: int = 1
    mtp_weight: float = 0.3
    compress_pod: bool = False
    pod_axis: str = "pod"
    ef_dtype: str = "bfloat16"   # error-feedback buffer dtype


def _microbatch(batch: dict, n: int) -> dict:
    """(B, ...) leaves → (n, B/n, ...) with microbatch rows **strided**:
    microbatch j = rows {i·n + j}.

    The stride matters for sharding: the global batch is sharded over the
    data axis in contiguous blocks, so cutting contiguous microbatches
    puts the *sharded* dimension on the scan axis — each scan iteration's
    rows then live on one chip and GSPMD replicates the step across the
    rest (a measured 16× traffic/FLOP blow-up on every train cell).
    Strided cutting keeps every microbatch spread over all data shards.

    ``positions3`` carries batch on dim 1 (M-RoPE's (3, B, S) layout).
    """
    def cut(x, bdim=0):
        b = x.shape[bdim]
        assert b % n == 0, f"global batch {b} not divisible by accum {n}"
        shp = x.shape[:bdim] + (b // n, n) + x.shape[bdim + 1:]
        return jnp.moveaxis(x.reshape(shp), bdim + 1, 0)
    return {k: cut(v, 1 if k == "positions3" else 0)
            for k, v in batch.items()}


def _grads_of(cfg: ModelConfig, ctx: ExecContext, hp: TrainHParams):
    """(params, batch) → (loss, grads) with microbatch accumulation."""
    def loss_fn(p, b):
        return lm.loss_fn(p, b, cfg, ctx, mtp_weight=hp.mtp_weight)[0]

    vg = jax.value_and_grad(loss_fn)

    if hp.grad_accum == 1:
        return vg

    def accum(params, batch):
        mb = _microbatch(batch, hp.grad_accum)

        def body(carry, b):
            acc_l, acc_g = carry
            l, g = vg(params, b)
            acc_g = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32), acc_g, g)
            return (acc_l + l, acc_g), None

        zero_g = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), zero_g), mb,
                                        length=hp.grad_accum)
        inv = 1.0 / hp.grad_accum
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    return accum


def build_train_step(cfg: ModelConfig, ctx: ExecContext,
                     opt_cfg: AdamWConfig, hp: TrainHParams) -> Callable:
    """Returns ``train_step(params, opt_state, batch[, ef])``."""
    grads_of = _grads_of(cfg, ctx, hp)

    def schedule(step):
        return warmup_cosine(step, peak_lr=hp.peak_lr,
                             warmup_steps=hp.warmup_steps,
                             total_steps=hp.total_steps)

    if not hp.compress_pod:
        def train_step(params, opt_state, batch):
            loss, grads = grads_of(params, batch)
            lr = schedule(opt_state["step"])
            params, opt_state, om = adamw_update(params, grads, opt_state,
                                                 opt_cfg, lr=lr)
            return params, opt_state, {"loss": loss, **om}
        return train_step

    # --- compressed cross-pod variant -----------------------------------
    # The body is manual over *every* mesh axis: the batch is sharded over
    # pod+data explicitly, model compute is replicated shard-locally
    # (mesh=None strips nested constraints/shard_maps) and the in-pod data
    # reduction is an explicit pmean.  (A body manual over ``pod`` alone,
    # auto over data/model, trips an XLA SPMD-partitioner CHECK.)
    axis = hp.pod_axis
    ef_dtype = jnp.dtype(hp.ef_dtype)
    data_axes = tuple(a for a in ctx.batch_axes if a != axis)
    grads_of_inner = _grads_of(cfg, ctx.with_(mesh=None, batch_axes=()), hp)

    def train_step(params, opt_state, batch, ef):
        if ctx.mesh is None or axis not in ctx.mesh.axis_names:
            raise ValueError(f"compress_pod needs mesh axis {axis!r}")

        def pod_body(p, b, e):
            loss, grads = grads_of_inner(p, b)
            for a in data_axes:        # exact in-pod (ICI) mean
                loss = jax.lax.pmean(loss, a)
                grads = jax.tree.map(
                    lambda g, a=a: jax.lax.pmean(g, a), grads)
            e32 = jax.tree.map(lambda x: x.astype(jnp.float32), e)
            grads, e32 = compressed_psum_mean(grads, e32, axis)
            new_e = jax.tree.map(lambda x: x.astype(ef_dtype), e32)
            return jax.lax.pmean(loss, axis), grads, new_e

        pspec = jax.tree.map(lambda _: P(), params)
        bspec = {k: P((axis,) + data_axes) for k in batch}
        espec = jax.tree.map(lambda _: P(), ef)
        gspec = jax.tree.map(lambda _: P(), params)
        fn = jax.shard_map(pod_body, mesh=ctx.mesh,
                           in_specs=(pspec, bspec, espec),
                           out_specs=(P(), gspec, espec), check_vma=False)
        loss, grads, ef = fn(params, batch, ef)
        lr = schedule(opt_state["step"])
        params, opt_state, om = adamw_update(params, grads, opt_state,
                                             opt_cfg, lr=lr)
        return params, opt_state, {"loss": loss, **om}, ef

    return train_step


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def sample_logits(logits, key, *, temperature: float = 0.0, top_k: int = 0):
    """logits (B, 1, V) → tokens (B, 1) int32."""
    lg = logits[:, -1, :].astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
    lg = lg / temperature
    if top_k > 0:
        kth = jax.lax.top_k(lg, top_k)[0][..., -1:]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)[:, None]


def _pad_caches(caches, cfg: ModelConfig, max_len: int):
    """Grow every seq-extent cache leaf to ``max_len`` (zero-fill tail)."""
    def pad_leaf(key_name, t):
        if key_name in ("k", "v"):              # (k, B, Hkv, S, dh)
            s = t.shape[3]
            if s >= max_len:
                return t
            return jnp.pad(t, ((0, 0),) * 3 + ((0, max_len - s), (0, 0)))
        if key_name in ("c_kv", "k_rope"):      # (k, B, S, R)
            s = t.shape[2]
            if s >= max_len:
                return t
            return jnp.pad(t, ((0, 0), (0, 0), (0, max_len - s), (0, 0)))
        return t                                 # conv/ssm/xk/xv: fixed size

    def walk(c):
        if isinstance(c, dict):
            return {k: (walk(v) if isinstance(v, dict) else pad_leaf(k, v))
                    for k, v in c.items()}
        if isinstance(c, list):
            return [walk(v) for v in c]
        return c

    return walk(caches)


def build_serve_steps(cfg: ModelConfig, ctx: ExecContext, *,
                      max_len: int, temperature: float = 0.0,
                      top_k: int = 0):
    """Returns (prefill_step, decode_step).

    prefill_step(params, batch, key) -> (token, caches, length, enc_out)
    decode_step(params, token, caches, length, key[, enc_out])
        -> (next_token, caches, length+1)
    """
    def prefill_step(params, batch, key):
        logits, caches, enc_out = lm.prefill(params, batch, cfg, ctx)
        caches = _pad_caches(caches, cfg, max_len)
        tok = sample_logits(logits, key, temperature=temperature, top_k=top_k)
        length = jnp.asarray(batch["tokens"].shape[1], jnp.int32)
        return tok, caches, length, enc_out

    def decode_step(params, token, caches, length, key):
        logits, caches = lm.decode_step(params, token, caches, length, cfg,
                                        ctx)
        tok = sample_logits(logits, key, temperature=temperature, top_k=top_k)
        return tok, caches, length + 1

    return prefill_step, decode_step
