"""The yardstick's fixed numbers: the chip's peaks and the algorithmic work
of one lattice-site update.

Neither reads the program.  A roofline share built on them reads the
same work whatever executor, fusion mode or layout implements the step.
"""
from __future__ import annotations

#: Published peaks per chip, keyed by `device_kind` as JAX reports it.
#: "TPU v5 lite" (TPU v5e): 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s,
#: 1,600 Gbit/s chip-to-chip -- Google Cloud documentation, "TPU v5e"
#: (the same row as the program's `costmodel.DEVICE_PEAKS`).
PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9},
}

#: HBM bytes one site update must move: f and g, 19 float32 each, read
#: once and written once: 2 fields * 19 * 4 B * 2 = 304 B.
BYTES_PER_SITE = 2 * 19 * 4 * 2

#: Floating-point operations of one site update in the plain reference
#: (`bench/references/d3q19_binary_fluid.py`, `collide`): its adds,
#: subtracts, multiplies and divides per site, counted from the
#: reference's jaxpr (`count_reference_flops`).  Negations are left out
#: (they fold into an add), and so are the streaming shifts, which move
#: data and compute nothing.
FLOPS_PER_SITE = 660


def peaks(device_kind: str) -> dict:
    """The peaks of `device_kind`; a kind with no row is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} has no row in bench/lib/work.py "
                       f"PEAKS (known: {sorted(PEAKS)})") from None


def least_step_seconds(sites: int, device_kind: str) -> tuple[float, str]:
    """The least time one step over `sites` sites of one chip can take,
    and which term bounds it (`"bytes"` or `"flops"`)."""
    p = peaks(device_kind)
    t_bytes = sites * BYTES_PER_SITE / p["hbm_bw"]
    t_flops = sites * FLOPS_PER_SITE / p["peak_flops"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def count_reference_flops() -> int:
    """Recount `FLOPS_PER_SITE` from the reference's jaxpr."""
    import jax
    import jax.numpy as jnp

    from bench.references import d3q19_binary_fluid as ref

    phys = dict(A=0.125, B=0.125, kappa=0.02, tau=1.0, tau_phi=1.0, gamma=1.0)
    shape = (ref.NVEL, 2, 2, 2)
    jaxpr = jax.make_jaxpr(lambda f, g: ref.collide(f, g, phys))(
        jnp.ones(shape), jnp.ones(shape)).jaxpr
    sites = 8
    return sum(e.outvars[0].aval.size // sites for e in jaxpr.eqns
               if e.primitive.name in ("add", "sub", "mul", "div"))
