"""Gradient compression for the cross-pod (DCN) axis.

Multi-pod training reduces gradients twice: fast ICI reduction inside a pod
(uncompressed — ICI is cheap) and a slow DCN reduction across pods.  The
DCN hop is where compression pays: int8 absmax block quantisation with
**error feedback** (the quantisation residual is carried into the next
step's payload, the classic EF recipe that keeps compressed SGD/Adam
convergent).

Exactness on the wire: per-pod scales differ, so a plain psum of int8 codes
is *not* the true sum.  We instead ``all_gather`` the int8 codes (+ fp32
per-block scales, negligible) and form the weighted sum locally — exact
reconstruction of Σ_p dequant_p, and the HLO carries ``all-gather(s8)``:
n·(P-1)/P bytes per chip vs 2·n·(P-1)/P·4 bytes for an fp32 ring
all-reduce ⇒ ~8× fewer cross-pod bytes (P = pod count).  §Perf measures
the delta on the multi-pod mesh.  For large P a hierarchical
(quantise → reduce-scatter int8 → re-quantise → all-gather) ladder drops
the gather term to 2·n/P·1 B; with P=2 pods the flat gather is already
optimal.

``compressed_psum_mean`` must run *inside* ``shard_map`` where ``axis`` is
a manual axis (see ``repro.runtime.steps``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def compress_init(grads):
    """Zero error-feedback buffers, twin to the grad tree (fp32)."""
    return jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads)


def _quantize(x, block: int):
    flat = x.reshape(-1)
    n = flat.shape[0]
    npad = -(-n // block) * block
    fb = jnp.pad(flat, (0, npad - n)).reshape(-1, block)
    scale = jnp.max(jnp.abs(fb), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.round(fb / safe * 127.0), -127, 127).astype(jnp.int8)
    return codes, (scale / 127.0).astype(jnp.float32), n


def compressed_psum_mean(grads, error, axis: str, *, block: int = 1024):
    """EF-int8 mean-all-reduce of a grad tree over manual axis ``axis``.

    Returns ``(mean fp32 grads, new error buffers)``.
    """
    npods = jax.lax.axis_size(axis)

    def one(g, e):
        x = g.astype(jnp.float32) + e
        codes, scale, n = _quantize(x, block)
        sent = (codes.astype(jnp.float32) * scale).reshape(-1)[:n] \
            .reshape(g.shape)
        new_e = x - sent                            # residual → next step
        all_codes = jax.lax.all_gather(codes, axis)     # (P, nb, block) int8
        all_scale = jax.lax.all_gather(scale, axis)     # (P, nb, 1) fp32
        total = (all_codes.astype(jnp.float32) * all_scale).sum(0)
        total = total.reshape(-1)[:n].reshape(g.shape)
        return total / npods, new_e

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = tdef.flatten_up_to(error)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    return (tdef.unflatten([o[0] for o in outs]),
            tdef.unflatten([o[1] for o in outs]))
