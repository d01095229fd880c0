"""Attention: GQA, sliding window, softcap, qk-norm, cross-attn, KV cache.

Three execution paths, one weight layout:

* **train/prefill** — flash-attention kernel (Pallas) or jnp oracle,
  selected by ``ctx.backend``;
* **decode (heads-local)** — single-token einsum attention over the cache;
* **decode (sequence-sharded)** — the KV cache is sharded over the model
  axis along the *sequence* dimension; each shard computes partial
  (out·softmax-numerator, logsumexp) and the exact result is reassembled
  with two ``psum``s (flash-decoding).  This is what makes 32k×128 and
  500k-token caches fit: no chip ever holds the full KV.

Cache layout per layer: ``{"k": (B, Hkv, S_max, Dh), "v": ..., }`` with a
scalar ``length`` carried beside the tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from .config import AttnConfig, ModelConfig
from .context import ExecContext
from . import layers


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim)


def _qk_normalize(p, q, k, ctx):
    """Per-head RMSNorm of q and k (gemma3)."""
    def nrm(w, t):
        tf = t.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(tf * tf, axis=-1, keepdims=True) + 1e-6)
        return (tf * inv * (1.0 + w.astype(jnp.float32))).astype(t.dtype)
    return nrm(p["q_norm"], q), nrm(p["k_norm"], k)


def project_qkv(p, x, a: AttnConfig, ctx: ExecContext, rope=None):
    """x: (B, S, D) → q (B,S,H,dh), k/v (B,S,Hkv,dh), rope applied."""
    q = _split_heads(x @ p["wq"], a.n_heads, a.head_dim)
    k = _split_heads(x @ p["wk"], a.n_kv_heads, a.head_dim)
    v = _split_heads(x @ p["wv"], a.n_kv_heads, a.head_dim)
    if a.qk_norm:
        q, k = _qk_normalize(p, q, k, ctx)
    if rope is not None:
        cos, sin = rope
        q = layers.apply_rope(q, cos, sin)
        k = layers.apply_rope(k, cos, sin)
    return q, k, v


def _use_seq_parallel(ctx: ExecContext, a: AttnConfig, s: int) -> bool:
    """Sequence-parallel attention: when the head count doesn't divide the
    model axis, GSPMD would replicate the whole attention across it (a
    measured TP×-FLOP waste on phi3/qwen2-vl/gemma2).  Instead shard the
    *query sequence* over the model axis: each chip runs the flash kernel
    on S/TP query rows against full K/V, masks offset by its shard index.
    Exact, collective-free in forward (K/V already replicated), one psum
    of dK/dV in backward (inserted by shard_map's transpose)."""
    if not (ctx.seq_parallel_attn and ctx.mesh is not None
            and ctx.model_axis and ctx.backend == "xla"
            and ctx.attn_impl == "chunked"):
        return False
    tp = ctx.mesh.shape[ctx.model_axis]
    if a.n_heads % tp == 0:       # heads shard fine — TP handles it
        return False
    return s % tp == 0


def _seq_parallel_attention(qT, kT, vT, a: AttnConfig, ctx: ExecContext, *,
                            causal, window):
    mesh, axis = ctx.mesh, ctx.model_axis
    tp = mesh.shape[axis]
    s = qT.shape[2]
    s_local = s // tp
    bspec = _batch_subspec(ctx, qT.shape[0])

    def body(q_l, k_f, v_f):
        return ops.flash_attention(
            q_l, k_f, v_f, causal=causal, window=window, softcap=a.softcap,
            scale=a.scale, backend=ctx.backend,
            block_q=min(ctx.attn_block_q, s_local),
            impl="chunked", q_offset=(axis, s_local))

    fn = jax.shard_map(
        body, mesh=ctx.shard_map_mesh,
        in_specs=(P(bspec, None, axis, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, None, axis, None), check_vma=False)
    return fn(qT, kT, vT)


def full_attention(p, x, a: AttnConfig, ctx: ExecContext, *, rope=None,
                   causal=True, window=0, kv_override=None):
    """Bidirectional/causal full-sequence attention (train, prefill, encoder).

    kv_override: (k, v) already projected — used by cross-attention.
    Returns (out (B,S,D), (k, v)) so prefill can seed the cache.
    """
    if kv_override is None:
        q, k, v = project_qkv(p, x, a, ctx, rope=rope)
    else:
        q = _split_heads(x @ p["wq"], a.n_heads, a.head_dim)
        if a.qk_norm:
            q, _ = _qk_normalize(p, q, q, ctx)
        if rope is not None:
            q = layers.apply_rope(q, *rope)
        k, v = kv_override

    qT, kT, vT = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    if _use_seq_parallel(ctx, a, qT.shape[2]):
        o = _seq_parallel_attention(qT, kT, vT, a, ctx, causal=causal,
                                    window=window)
    else:
        o = ops.flash_attention(
            qT, kT, vT,
            causal=causal, window=window, softcap=a.softcap, scale=a.scale,
            backend=ctx.backend, block_q=ctx.attn_block_q,
            block_k=ctx.attn_block_k, impl=ctx.attn_impl)
    b, s = x.shape[:2]
    out = o.transpose(0, 2, 1, 3).reshape(b, s, a.n_heads * a.head_dim)
    return out @ p["wo"], (k, v)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _decode_scores_to_out(q, k, v, a: AttnConfig, length, window=0,
                          key_positions=None):
    """Single-token attention over a cache; all-local math.

    q: (B, H, 1, dh); k/v: (B, Hkv, S, dh).  Masks positions >= length and,
    for sliding-window layers, positions <= length-1-window.
    ``key_positions``: per-slot global positions (ring buffers); default
    ``arange(S)``; negative positions = never-written slots.
    Returns (out (B,H,1,dh) *unnormalised*, lse-style stats) so callers can
    combine shards exactly: out_num = sum(p̃·v), denom = sum(p̃), with
    p̃ = exp(s - m), plus the local max m.
    """
    group = a.n_heads // a.n_kv_heads
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    scale = a.scale if a.scale is not None else a.head_dim ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if a.softcap > 0:
        s = a.softcap * jnp.tanh(s / a.softcap)
    pos = jnp.arange(k.shape[2]) if key_positions is None else key_positions
    mask = (pos[None, None, None, :] < length) & \
        (pos[None, None, None, :] >= 0)
    if window > 0:
        mask = mask & (pos[None, None, None, :] > length - 1 - window)
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)                       # (B,H,1,1)
    # guard fully-masked shards
    m_safe = jnp.where(m <= -1e29, 0.0, m)
    pt = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    num = jnp.einsum("bhqk,bhkd->bhqd", pt, vr.astype(jnp.float32))
    den = pt.sum(-1, keepdims=True)                              # (B,H,1,1)
    return num, den, m_safe


def decode_attention(p, x, a: AttnConfig, ctx: ExecContext, cache, length, *,
                     rope=None, window=0, cross=False):
    """One-token attention step.

    x: (B, 1, D); cache: {"k","v"} (B, Hkv, S_max, dh) (sharded along S over
    the model axis when ctx.seq_shard_decode).  Returns (out, new_cache).
    """
    b = x.shape[0]
    key_positions = None
    if cross:
        q = _split_heads(x @ p["wq"], a.n_heads, a.head_dim)
        if rope is not None:
            q = layers.apply_rope(q, *rope)
        k, v, new_cache = cache["k"], cache["v"], cache
    else:
        q, k_new, v_new = project_qkv(p, x, a, ctx, rope=rope)
        k_new = k_new.transpose(0, 2, 1, 3)                      # (B,Hkv,1,dh)
        v_new = v_new.transpose(0, 2, 1, 3)
        w_cache = cache["k"].shape[2]
        ring = window > 0 and w_cache == window
        # ring buffers (local layers, window-sized cache): write at
        # length mod W; slot i then holds global position
        # length - ((slot - i) mod W), negative = never written.
        write_at = (jnp.mod(jnp.asarray(length), w_cache) if ring
                    else length)
        k = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new.astype(cache["k"].dtype), write_at, axis=2)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new.astype(cache["v"].dtype), write_at, axis=2)
        new_cache = {"k": k, "v": v}
        if ring:
            idx = jnp.arange(w_cache)
            key_positions = length - jnp.mod(write_at - idx, w_cache)

    qt = q.transpose(0, 2, 1, 3)                                 # (B,H,1,dh)
    # cross-attention attends to the full (static-length) encoder memory
    new_len = k.shape[2] if cross else length + 1

    if key_positions is None and _can_seq_shard(ctx, k.shape[2]):
        out = _seq_sharded_decode(qt, k, v, a, ctx, new_len, window)
    else:
        num, den, _ = _decode_scores_to_out(qt, k, v, a, new_len, window,
                                            key_positions=key_positions)
        out = num / jnp.maximum(den, 1e-30)
    out = out.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, 1, -1)
    return out @ p["wo"], new_cache


def _can_seq_shard(ctx: ExecContext, smax: int) -> bool:
    """Flash-decoding applies only when the cache's sequence extent divides
    the model axis (whisper's 1500-frame cross cache, e.g., does not)."""
    if not (ctx.seq_shard_decode and ctx.mesh is not None and ctx.model_axis):
        return False
    return smax % ctx.mesh.shape[ctx.model_axis] == 0


def _batch_subspec(ctx: ExecContext, b: int):
    """Batch dim mesh axes, dropped when the batch doesn't divide them
    (long_500k decodes batch=1 on a 16-wide data axis → replicate)."""
    if not ctx.batch_axes:
        return None
    n = 1
    for ax in ctx.batch_axes:
        n *= ctx.mesh.shape[ax]
    return ctx.batch_axes if b % n == 0 else None


def _seq_sharded_decode(q, k, v, a: AttnConfig, ctx: ExecContext, length,
                        window):
    """Flash-decoding over a sequence-sharded cache.

    Runs under ``shard_map``: every model-axis shard holds a contiguous
    S_max/TP slice of the cache; partial (num, den) are combined with psum
    after rescaling by the global max — exact softmax, 2 small collectives.
    """
    axis = ctx.model_axis
    mesh = ctx.mesh
    smax = k.shape[2]
    tp = mesh.shape[axis]

    def body(q_l, k_l, v_l, length_l):
        shard = jax.lax.axis_index(axis)
        offset = shard * (smax // tp)
        # local positions → global positions for masking
        pos = offset + jnp.arange(k_l.shape[2])
        group = a.n_heads // a.n_kv_heads
        kr = jnp.repeat(k_l, group, axis=1)
        vr = jnp.repeat(v_l, group, axis=1)
        scale = a.scale if a.scale is not None else a.head_dim ** -0.5
        s = jnp.einsum("bhqd,bhkd->bhqk", q_l.astype(jnp.float32),
                       kr.astype(jnp.float32)) * scale
        if a.softcap > 0:
            s = a.softcap * jnp.tanh(s / a.softcap)
        mask = pos[None, None, None, :] < length_l
        if window > 0:
            mask = mask & (pos[None, None, None, :] > length_l - 1 - window)
        s = jnp.where(mask, s, -1e30)
        m_loc = jnp.max(s, axis=-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, axis)
        m_safe = jnp.where(m_glob <= -1e29, 0.0, m_glob)
        pt = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        num = jnp.einsum("bhqk,bhkd->bhqd", pt, vr.astype(jnp.float32))
        den = pt.sum(-1, keepdims=True)
        num = jax.lax.psum(num, axis)
        den = jax.lax.psum(den, axis)
        return num / jnp.maximum(den, 1e-30)

    # Specs: batch stays on its axes; cache sequence axis is sharded on the
    # model axis; q is replicated over the model axis.
    bspec = _batch_subspec(ctx, q.shape[0])
    in_specs = (P(bspec, None, None, None),
                P(bspec, None, axis, None),
                P(bspec, None, axis, None),
                P())
    out_spec = P(bspec, None, None, None)
    fn = jax.shard_map(body, mesh=ctx.shard_map_mesh, in_specs=in_specs,
                       out_specs=out_spec, check_vma=False)
    return fn(q, k, v, jnp.asarray(length))
