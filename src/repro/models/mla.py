"""Multi-head Latent Attention (DeepSeek-V2/V3).

Two paths over one weight set:

* **expanded** (train / prefill): decompress the latent to per-head K/V and
  run ordinary flash attention with qk head_dim = nope+rope (192) and V
  padded to the same width (sliced after) so a single kernel signature
  serves all archs.
* **absorbed** (decode): the cache stores only the 512-dim KV latent plus
  the 64-dim shared rope key per token (*this* is MLA's memory win:
  576 B/token/layer in bf16 instead of 128 heads × 256).  The up-projection
  is absorbed into the query/output sides:
      score(h) = (q_nope(h) Wᵤᵏ(h)ᵀ) · c_kv + q_rope(h) · k_rope
      out(h)   = (softmax · c_kv) Wᵤᵛ(h)
  Optionally sequence-sharded over the model axis (flash-decoding combine),
  since even the latent cache at 500k tokens wants sharding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from .config import MLAConfig, ModelConfig
from .context import ExecContext
from . import layers


def _rms(w, x):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (xf * inv * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _project_q(p, x, cfg: ModelConfig):
    m, h = cfg.mla, cfg.attn.n_heads
    b, s, _ = x.shape
    cq = _rms(p["q_norm"], x @ p["w_dq"])
    q = (cq @ p["w_uq"]).reshape(b, s, h, m.nope_head_dim + m.rope_head_dim)
    return q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]


def _latent_kv(p, x, cfg: ModelConfig, rope):
    """c_kv (B,S,R) and rope'd shared key k_rope (B,S,rope_dim)."""
    m = cfg.mla
    c_kv = _rms(p["kv_norm"], x @ p["w_dkv"])
    k_rope = (x @ p["w_kr"])[:, :, None, :]           # (B,S,1,rope)
    k_rope = layers.apply_rope(k_rope, *rope)[:, :, 0, :]
    return c_kv, k_rope


def mla_full(p, x, cfg: ModelConfig, ctx: ExecContext, *, rope, causal=True):
    """Expanded-path attention; returns (out, (c_kv, k_rope)) for the cache."""
    m, a = cfg.mla, cfg.attn
    h = a.n_heads
    b, s, _ = x.shape
    qk_dim = m.nope_head_dim + m.rope_head_dim

    q_nope, q_rope = _project_q(p, x, cfg)
    q_rope = layers.apply_rope(q_rope, *rope)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)    # (B,S,H,192)

    c_kv, k_rope = _latent_kv(p, x, cfg, rope)
    k_nope = (c_kv @ p["w_uk"]).reshape(b, s, h, m.nope_head_dim)
    v = (c_kv @ p["w_uv"]).reshape(b, s, h, m.v_head_dim)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, m.rope_head_dim))],
        axis=-1)

    # pad V to the qk width so one flash kernel signature serves both
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - m.v_head_dim)))
    scale = a.scale if a.scale is not None else qk_dim ** -0.5
    o = ops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v_pad.transpose(0, 2, 1, 3),
        causal=causal, softcap=a.softcap, scale=scale,
        backend=ctx.backend, block_q=ctx.attn_block_q,
        block_k=ctx.attn_block_k, impl=ctx.attn_impl)
    o = o.transpose(0, 2, 1, 3)[..., :m.v_head_dim].reshape(b, s, -1)
    return o @ p["wo"], (c_kv, k_rope)


def mla_decode(p, x, cfg: ModelConfig, ctx: ExecContext, cache, length, *,
               rope):
    """Absorbed-path single-token step over the latent cache.

    cache: {"c_kv": (B, S_max, R), "k_rope": (B, S_max, rope_dim)}.
    """
    m, a = cfg.mla, cfg.attn
    h = a.n_heads
    b = x.shape[0]
    qk_dim = m.nope_head_dim + m.rope_head_dim
    scale = a.scale if a.scale is not None else qk_dim ** -0.5

    q_nope, q_rope = _project_q(p, x, cfg)            # (B,1,H,·)
    q_rope = layers.apply_rope(q_rope, *rope)

    c_new, kr_new = _latent_kv(p, x, cfg, rope)       # (B,1,R), (B,1,rope)
    c_kv = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_new.astype(cache["c_kv"].dtype), length, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), length, axis=1)
    new_cache = {"c_kv": c_kv, "k_rope": k_rope}
    new_len = length + 1

    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.nope_head_dim)
    # absorb: q_abs (B,H,R) = q_nope · W_uk(h)ᵀ
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))

    from .attention import _can_seq_shard
    if _can_seq_shard(ctx, c_kv.shape[1]):
        o_lat = _mla_seq_sharded(q_abs, q_rope[:, 0], c_kv, k_rope, ctx,
                                 new_len, scale)
    else:
        s = (jnp.einsum("bhr,bsr->bhs", q_abs, c_kv.astype(jnp.float32))
             + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32),
                          k_rope.astype(jnp.float32))) * scale
        pos = jnp.arange(c_kv.shape[1])
        s = jnp.where(pos[None, None, :] < new_len, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhs,bsr->bhr", pr, c_kv.astype(jnp.float32))

    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32))
    o = o.reshape(b, 1, h * m.v_head_dim).astype(x.dtype)
    return o @ p["wo"], new_cache


def _mla_seq_sharded(q_abs, q_rope, c_kv, k_rope, ctx: ExecContext, length,
                     scale):
    """Flash-decoding combine over a latent cache sharded along sequence."""
    from .attention import _batch_subspec
    axis = ctx.model_axis
    smax = c_kv.shape[1]
    tp = ctx.mesh.shape[axis]
    bspec = _batch_subspec(ctx, q_abs.shape[0])

    def body(qa, qr, ck, kr, ln):
        shard = jax.lax.axis_index(axis)
        pos = shard * (smax // tp) + jnp.arange(ck.shape[1])
        s = (jnp.einsum("bhr,bsr->bhs", qa, ck.astype(jnp.float32))
             + jnp.einsum("bhd,bsd->bhs", qr.astype(jnp.float32),
                          kr.astype(jnp.float32))) * scale
        mask = pos[None, None, :] < ln
        s = jnp.where(mask, s, -1e30)
        m_loc = s.max(-1, keepdims=True)
        m_glob = jax.lax.pmax(m_loc, axis)
        m_safe = jnp.where(m_glob <= -1e29, 0.0, m_glob)
        pt = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        num = jnp.einsum("bhs,bsr->bhr", pt, ck.astype(jnp.float32))
        den = pt.sum(-1)[..., None]
        return jax.lax.psum(num, axis) / jnp.maximum(jax.lax.psum(den, axis), 1e-30)

    fn = jax.shard_map(
        body, mesh=ctx.shard_map_mesh,
        in_specs=(P(bspec, None, None), P(bspec, None, None),
                  P(bspec, axis, None), P(bspec, axis, None), P()),
        out_specs=P(bspec, None, None), check_vma=False)
    return fn(q_abs, q_rope, c_kv, k_rope, jnp.asarray(length))
