"""The comparison that decides `correct`.

One call of the window, drawn from the seed, is kept: its input fields
(copied before the call donates them), its output fields and the
statistics the host received.  Once the window has closed, the plain
reference runs the same number of steps from that input, and each number
below is held to its limit in the configuration file:

* `max_abs_df`, `max_abs_dg`: the largest gap of any population of that
  call's output from the reference's;
* `obs_gap`: the largest gap of the call's statistics from the
  reference's statistics of the reference's output, each over its own
  scale (a sum over the sum of magnitudes, an extreme over the largest
  magnitude, the variance over the mean square);
* `nan_calls`: the calls of the window whose statistics saw a NaN;
* `devices_missing` (on a mesh): devices of the mesh that hold no part
  of the kept output.

How each limit was set, from which readings, is in PERF.md.
"""
from __future__ import annotations

import numpy as np

SAMPLE_CALLS = 4      # the kept call is drawn from the window's first four


def sample_call(seed: int) -> int:
    return int(np.random.default_rng(int(seed) % 2 ** 64).integers(SAMPLE_CALLS))


def _scales(f, g) -> dict:
    import jax.numpy as jnp

    phi, rho = g.sum(0), f.sum(0)
    return {"mass": float(jnp.abs(rho).sum()), "phi_total": float(jnp.abs(phi).sum()),
            "phi_min": float(jnp.abs(phi).max()), "phi_max": float(jnp.abs(phi).max()),
            "phi_var": float((phi * phi).mean()), "rho_min": float(jnp.abs(rho).max())}


def field_numbers(f_out, g_out, f_ref, g_ref) -> dict:
    import jax.numpy as jnp

    return {"max_abs_df": float(jnp.abs(f_out - f_ref).max()),
            "max_abs_dg": float(jnp.abs(g_out - g_ref).max())}


def obs_gaps(obs: dict, f_ref, g_ref, ref) -> dict:
    """The call's statistics against those of the reference module `ref`,
    each over its scale; `obs_gap` is the largest."""
    want = ref.observables(f_ref, g_ref)
    scale = _scales(f_ref, g_ref)
    return {k: abs(float(obs[k]) - want[k]) / scale[k] for k in want}


def window_numbers(all_obs: list) -> dict:
    return {"nan_calls": sum(bool(o["nan"]) for o in all_obs)}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(`correct`, {name: {"value", "limit"}}) for every limited number."""
    out, ok = {}, True
    for name, limit in limits.items():
        if name not in numbers:
            continue
        if limit is None:
            raise ValueError(f"number {name!r} has no limit in the configuration")
        v = numbers[name]
        out[name] = {"value": v, "limit": limit}
        ok = ok and bool(v <= limit)      # a NaN fails
    missing = set(limits) - set(numbers)
    if missing:
        raise ValueError(f"numbers {sorted(missing)} were not computed")
    return ok, out
