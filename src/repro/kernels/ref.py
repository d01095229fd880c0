"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth for the allclose test sweeps, *and* the compute
path used by the CPU dry-run (Pallas TPU kernels do not lower to the CPU
backend; the single-source site-kernel bodies guarantee the math is
identical — that equivalence is what the kernel test sweeps pin down).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .lb_collision import CV, NVEL, WEIGHTS

# ---------------------------------------------------------------------------
# lattice Boltzmann binary collision
# ---------------------------------------------------------------------------


def lb_collision_ref(f, g, phi, gradphi, del2phi, *,
                     A=0.0625, B=0.0625, kappa=0.04,
                     tau=1.0, tau_phi=1.0, gamma=1.0):
    """Oracle over full SoA arrays ``(ncomp, nsites)``; mirrors
    :func:`repro.kernels.lb_collision.collision_site_kernel` — written
    independently (einsum over the whole lattice at once) but keeping the
    site kernel's exact accumulation/association order (``cu * cu``, not
    ``cu ** 2``; ``φ·φ·φ``), so the two compute the same numbers on the
    xla executor.  The Program-based driver leans on this: the unfused
    pipeline's collide stage (``COLLIDE_SPEC`` → the site kernel) must
    reproduce the historical ``ops.lb_collision`` trajectory (pinned by
    ``tests/test_program.py`` under the cross-path contract)."""
    dt = f.dtype
    w = jnp.asarray(WEIGHTS, dt)[:, None]
    c = jnp.asarray(CV, dt)
    phi_ = phi[0]
    mu = -A * phi_ + B * phi_ * phi_ * phi_ - kappa * del2phi[0]
    force = mu[None, :] * gradphi

    hi = jax.lax.Precision.HIGHEST         # float32 contractions on a TPU
    rho = f.sum(0)
    u = (jnp.einsum("qd,qv->dv", c, f, precision=hi)
         + 0.5 * force) / rho[None, :]
    cu = jnp.einsum("qd,dv->qv", c, u, precision=hi)
    usq = (u * u).sum(0)
    feq = w * rho[None, :] * (1.0 + 3.0 * cu + 4.5 * cu * cu
                              - 1.5 * usq[None, :])
    cf = jnp.einsum("qd,dv->qv", c, force, precision=hi)
    uf = (u * force).sum(0)
    fterm = (1.0 - 0.5 / tau) * w * (3.0 * (cf - uf[None, :])
                                     + 9.0 * cu * cf)
    f_out = f - (f - feq) / tau + fterm

    gt = w * (3.0 * gamma * mu[None, :] + 3.0 * phi_[None, :] * cu)
    g0 = phi_ - (gt.sum(0) - gt[0])
    geq = jnp.concatenate([g0[None, :], gt[1:]], axis=0)
    g_out = g - (g - geq) / tau_phi
    return f_out, g_out


# ---------------------------------------------------------------------------
# LM pointwise
# ---------------------------------------------------------------------------

def rmsnorm_ref(x, weight, *, eps=1e-6, scale_offset=0.0):
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv * (weight.astype(jnp.float32) + scale_offset)).astype(x.dtype)


def gated_act_ref(u, v=None, *, kind="swiglu"):
    uf = u.astype(jnp.float32)
    if kind in ("swiglu", "silu"):
        a = uf * jax.nn.sigmoid(uf)
    elif kind in ("geglu", "gelu"):
        a = jax.nn.gelu(uf, approximate=True)
    elif kind == "relu2":
        r = jnp.maximum(uf, 0.0)
        a = r * r
    else:
        raise ValueError(kind)
    out = a if v is None else a * v.astype(jnp.float32)
    return out.astype(u.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0, scale=None,
                  kv_len=None):
    """Oracle attention: q (B,Hq,Sq,Dh), k/v (B,Hkv,Sk,Dh)."""
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    kv_len = sk if kv_len is None else kv_len

    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    mask = k_pos < kv_len
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window > 0:
        mask = mask & (k_pos > q_pos - window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no live keys: softmax of all -1e30 is uniform; zero them.
    alive = mask.any(-1)[None, None, :, None]
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
    return jnp.where(alive, out, 0.0).astype(q.dtype)


def _blk_scores(qblk, kr, i, bq, sk, *, causal, window, softcap, scale,
                q_offset=0):
    """(scores, mask) for one q block — shared by fwd and recompute-bwd.

    ``q_offset``: int, or ``(axis_name, s_local)`` for sequence-parallel
    callers — the offset is then ``axis_index(axis)·s_local``, resolved
    inside the shard_map body (static under SPMD).  K stays in its input
    dtype (bf16 on the real path) with fp32 accumulation — pre-casting
    K/V to fp32 doubled the dominant decode/train buffers."""
    s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kr,
                   preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    if isinstance(q_offset, tuple):
        axis_name, s_local = q_offset
        q_offset = jax.lax.axis_index(axis_name) * s_local
    q_pos = q_offset + i * bq + jnp.arange(bq)
    k_pos = jnp.arange(sk)
    mask = jnp.ones((bq, sk), bool)
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window > 0:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    return jnp.where(mask[None, None], s, -1e30), mask


def _chunk_fwd(q, k, v, cfg):
    """Returns (out, lse).  lse is per-row logsumexp (B, Hq, Sq_padded)."""
    causal, window, softcap, scale, block_q, q_offset = cfg
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    bq = min(block_q, sq)
    npad = -(-sq // bq) * bq - sq
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, npad), (0, 0))) if npad else q
    nblk = qp.shape[2] // bq
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)

    def body(_, qi):
        qblk, i = qi
        s, mask = _blk_scores(qblk, kr, i, bq, sk, causal=causal,
                              window=window, softcap=softcap, scale=scale,
                              q_offset=q_offset)
        m = jnp.max(s, axis=-1, keepdims=True)
        m_safe = jnp.where(m <= -1e29, 0.0, m)
        pt = jnp.exp(s - m_safe)
        l = pt.sum(-1, keepdims=True)
        alive = mask.any(-1)[None, None, :, None]
        o = jnp.einsum("bhqk,bhkd->bhqd", pt, vr,
                       preferred_element_type=jnp.float32) \
            / jnp.maximum(l, 1e-30)
        lse = jnp.where(alive[..., 0], m_safe[..., 0] + jnp.log(
            jnp.maximum(l[..., 0], 1e-30)), -1e30)
        return None, (jnp.where(alive, o, 0.0).astype(q.dtype), lse)

    qs = jnp.moveaxis(qp.reshape(b, hq, nblk, bq, dh), 2, 0)
    _, (os_, lses) = jax.lax.scan(body, None, (qs, jnp.arange(nblk)))
    out = jnp.moveaxis(os_, 0, 2).reshape(b, hq, nblk * bq, dh)
    lse = jnp.moveaxis(lses, 0, 2).reshape(b, hq, nblk * bq)
    return out[:, :, :sq], lse[:, :, :sq]


def _chunk_bwd(cfg, res, dout):
    """Flash-style backward: recompute per-block probabilities from the
    saved logsumexp instead of saving S² probabilities — this is the
    memory behaviour of the real TPU kernel (and removes the dominant
    traffic term the dry-run measured on every train cell)."""
    causal, window, softcap, scale, block_q, q_offset = cfg
    q, k, v, out, lse = res
    b, hq, sq, dh = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    bq = min(block_q, sq)
    npad = -(-sq // bq) * bq - sq

    def pad_q(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, npad)) +
                       ((0, 0),) * (x.ndim - 3)) if npad else x

    qp, outp, doutp = pad_q(q), pad_q(out), pad_q(dout)
    lsep = pad_q(lse)
    nblk = qp.shape[2] // bq
    kr = jnp.repeat(k, group, axis=1)
    vr = jnp.repeat(v, group, axis=1)
    # D_i = Σ_d dout·out per row — the softmax-jacobian diagonal term
    Dp = (doutp.astype(jnp.float32) * outp.astype(jnp.float32)).sum(-1)

    def body(carry, qi):
        dkr_acc, dvr_acc = carry
        qblk, doblk, dblk, lseblk, i = qi
        s, mask = _blk_scores(qblk, kr, i, bq, sk, causal=causal,
                              window=window, softcap=softcap, scale=scale,
                              q_offset=q_offset)
        p = jnp.exp(s - lseblk[..., None])            # normalised probs
        p = jnp.where(mask[None, None], p, 0.0)
        do = doblk.astype(jnp.float32)
        dvr_acc = dvr_acc + jnp.einsum("bhqk,bhqd->bhkd", p, do)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, vr,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - dblk[..., None])               # d(softcapped scores)
        if softcap > 0:
            # s here is post-cap; d(raw) = d(capped)·(1 - (s/c)²)
            ds = ds * (1.0 - jnp.square(
                jnp.where(mask[None, None], s, 0.0) / softcap))
        ds = jnp.where(mask[None, None], ds, 0.0)
        dq_blk = jnp.einsum("bhqk,bhkd->bhqd", ds, kr,
                            preferred_element_type=jnp.float32) * scale
        dkr_acc = dkr_acc + jnp.einsum(
            "bhqk,bhqd->bhkd", ds, qblk.astype(jnp.float32)) * scale
        return (dkr_acc, dvr_acc), dq_blk

    qs = jnp.moveaxis(qp.reshape(b, hq, nblk, bq, dh), 2, 0)
    dos = jnp.moveaxis(doutp.reshape(b, hq, nblk, bq, dh), 2, 0)
    Ds = jnp.moveaxis(Dp.reshape(b, hq, nblk, bq), 2, 0)
    lses = jnp.moveaxis(lsep.reshape(b, hq, nblk, bq), 2, 0)
    zero_k = jnp.zeros((b, hq, sk, dh), jnp.float32)
    (dkr, dvr), dqs = jax.lax.scan(
        body, (zero_k, zero_k), (qs, dos, Ds, lses, jnp.arange(nblk)))
    dq = jnp.moveaxis(dqs, 0, 2).reshape(b, hq, nblk * bq, dh)[:, :, :sq]
    # fold grouped-query heads back onto their kv head
    dk = dkr.reshape(b, hkv, group, sk, dh).sum(2)
    dv = dvr.reshape(b, hkv, group, sk, dh).sum(2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunked_attention(q, k, v, cfg):
    return _chunk_fwd(q, k, v, cfg)[0]


def _chunked_attention_fwd(q, k, v, cfg):
    out, lse = _chunk_fwd(q, k, v, cfg)
    return out, (q, k, v, out, lse)


_chunked_attention.defvjp(_chunked_attention_fwd, _chunk_bwd)


def attention_chunked_ref(q, k, v, *, causal=True, window=0, softcap=0.0,
                          scale=None, block_q=512, q_offset=0):
    """Memory-bounded oracle: identical math to :func:`attention_ref`, but
    the query axis is processed in ``block_q`` chunks under ``lax.scan``
    (live score buffer (B, H, block_q, Sk), not (B, H, Sq, Sk)) **and**
    the backward recomputes block probabilities from a saved logsumexp
    (flash-attention backward) instead of saving them.

    This is the compute path the dry-run cells lower — it reproduces the
    memory behaviour of the real Pallas TPU kernel on any backend.
    ``q_offset`` shifts the causal/window masks for sequence-parallel
    callers whose local block holds global positions [offset, offset+Sq).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    off = (q_offset if isinstance(q_offset, tuple)
           else int(q_offset))                       # hashable → static
    cfg = (bool(causal), int(window), float(softcap), float(scale),
           int(block_q), off)
    return _chunked_attention(q, k, v, cfg)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

def mamba_scan_ref(x, dt, b, c, a, d):
    """Step-by-step lax.scan oracle.  Shapes as mamba_scan_pallas."""
    batch, L, d_inner = x.shape
    n = a.shape[-1]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t[..., None] * a[None])          # (batch, d_inner, N)
        h = h * decay + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y_t = (h * c_t[:, None, :]).sum(-1) + d[None] * x_t
        return h, y_t

    h0 = jnp.zeros((batch, d_inner, n), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(b, 1, 0).astype(jnp.float32),
          jnp.moveaxis(c, 1, 0).astype(jnp.float32))
    h_final, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_final
