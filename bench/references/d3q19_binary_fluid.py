"""Plain reference of the D3Q19 binary-fluid step, kept with the benchmark.

It states the physics of the configurations `ludwig_spinodal_*` from
scratch: it imports nothing of the program under test and takes none of
its tables.  The state is two float32 arrays `f` and `g` of shape
`(19, X, Y, Z)`, both in the velocity order of `CV` below (the order in
which the program stores its populations, which is part of the state's
format).  One step, as Ludwig's binary fluid with a Guo-type force:

    phi = sum_q g_q
    grad phi, lap phi   six-point star, central differences (periodic)
    mu  = -A phi + B phi^3 - kappa lap phi
    F   = mu grad phi
    rho = sum_q f_q,   u = (sum_q c_q f_q + F/2) / rho
    f_q <- f_q - (f_q - feq_q)/tau + (1 - 1/(2 tau)) w_q (3(c_q.F - u.F) + 9 (c_q.u)(c_q.F))
    g_q <- g_q - (g_q - geq_q)/tau_phi
    stream: f_q(x + c_q) <- f_q(x), the same for g

with feq_q = w_q rho (1 + 3 c_q.u + 9/2 (c_q.u)^2 - 3/2 u.u),
geq_q = w_q (3 gamma mu + 3 phi c_q.u) for q >= 1 and
geq_0 = phi - sum_{q>=1} geq_q.

Every velocity contraction is written out as sums of the populations
(each entry of `CV` is 0 or +-1), so the reference runs in exact float32
arithmetic on any device: no matrix unit, no precision setting.

`precision` selects the control computations of the same step, used to
show that the benchmark's comparison fails a lower precision:

* `"float32"`: the reference;
* `"high"`: every velocity contraction takes its operand rounded as a
  three-pass bfloat16 product does (`high`, the tier below float32 at
  `highest`): x is split into bf16(x) + bf16(x - bf16(x));
* `"bf16_state"`: f and g are stored in bfloat16 between steps (the
  tier below float32 storage), the arithmetic stays float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NVEL = 19
CV = np.array(
    [[0, 0, 0],
     [1, 0, 0], [-1, 0, 0], [0, 1, 0], [0, -1, 0], [0, 0, 1], [0, 0, -1],
     [1, 1, 0], [1, -1, 0], [-1, 1, 0], [-1, -1, 0],
     [1, 0, 1], [1, 0, -1], [-1, 0, 1], [-1, 0, -1],
     [0, 1, 1], [0, 1, -1], [0, -1, 1], [0, -1, -1]], dtype=np.int64)
WEIGHTS = np.array([1 / 3] + [1 / 18] * 6 + [1 / 36] * 12)

PRECISIONS = ("float32", "high", "bf16_state")


def _split3(x):
    """x as a three-pass bfloat16 product sees it: hi + lo, each bf16."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi + lo


def _contract(rows, vecs):
    """sum_k rows[k] * vecs[k] for rows of 0/+-1: adds and subtracts only."""
    out = None
    for r, v in zip(rows, vecs):
        if r == 0:
            continue
        term = v if r > 0 else -v
        out = term if out is None else out + term
    return out if out is not None else jnp.zeros_like(vecs[0])


def collide(f, g, phys, precision="float32"):
    """Post-collision (f, g) at every site; the neighbours enter only
    through phi's six-point star."""
    A, B, kappa = phys["A"], phys["B"], phys["kappa"]
    tau, tau_phi, gamma = phys["tau"], phys["tau_phi"], phys["gamma"]
    w = [jnp.float32(x) for x in WEIGHTS]
    cin = _split3 if precision == "high" else (lambda x: x)

    phi = functools.reduce(jnp.add, [g[q] for q in range(NVEL)])
    nb = {(d, s): jnp.roll(phi, -s, axis=d) for d in range(3) for s in (1, -1)}
    grad = [0.5 * (nb[d, 1] - nb[d, -1]) for d in range(3)]
    lap = -6.0 * phi
    for d in range(3):
        lap = lap + nb[d, 1] + nb[d, -1]
    mu = -A * phi + B * phi * phi * phi - kappa * lap
    force = [mu * gd for gd in grad]

    rho = functools.reduce(jnp.add, [f[q] for q in range(NVEL)])
    fq = [cin(f[q]) for q in range(NVEL)]
    mom = [_contract(CV[:, d], fq) for d in range(3)]
    u = [(mom[d] + 0.5 * force[d]) / rho for d in range(3)]
    uc, fc = [cin(x) for x in u], [cin(x) for x in force]
    usq = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
    uf = u[0] * force[0] + u[1] * force[1] + u[2] * force[2]

    f_out, gt = [], []
    for q in range(NVEL):
        cu = _contract(CV[q], uc)
        cf = _contract(CV[q], fc)
        feq = w[q] * rho * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq)
        fterm = (1.0 - 0.5 / tau) * w[q] * (3.0 * (cf - uf) + 9.0 * cu * cf)
        f_out.append(f[q] - (f[q] - feq) / tau + fterm)
        gt.append(w[q] * (3.0 * gamma * mu + 3.0 * phi * cu))
    g0 = phi - functools.reduce(jnp.add, gt[1:])
    geq = [g0] + gt[1:]
    g_out = [g[q] - (g[q] - geq[q]) / tau_phi for q in range(NVEL)]
    return jnp.stack(f_out), jnp.stack(g_out)


def stream(x):
    """x_q(site + c_q) <- x_q(site), periodic."""
    return jnp.stack([jnp.roll(x[q], tuple(int(c) for c in CV[q]), axis=(0, 1, 2))
                      for q in range(NVEL)])


@functools.partial(jax.jit, static_argnames=("nsteps", "phys", "precision"))
def _run(f, g, *, nsteps, phys, precision):
    phys = dict(phys)

    def body(carry, _):
        f, g = carry
        fc, gc = collide(f, g, phys, precision)
        f, g = stream(fc), stream(gc)
        if precision == "bf16_state":
            f = f.astype(jnp.bfloat16).astype(jnp.float32)
            g = g.astype(jnp.bfloat16).astype(jnp.float32)
        return (f, g), None

    (f, g), _ = jax.lax.scan(body, (f, g), None, length=nsteps)
    return f, g


def run(f, g, nsteps: int, phys: dict, precision: str = "float32"):
    """`nsteps` steps from (f, g)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    if precision == "bf16_state":
        f = f.astype(jnp.bfloat16).astype(jnp.float32)
        g = g.astype(jnp.bfloat16).astype(jnp.float32)
    key = tuple(sorted((k, float(v)) for k, v in phys.items()))
    return _run(f, g, nsteps=int(nsteps), phys=key, precision=precision)


def observables(f, g) -> dict:
    """The statistics a run prints: the per-site sums in float32 where the
    fields live, the rest in float64 on the host."""
    rho = np.asarray(f.sum(0), dtype=np.float64)
    phi = np.asarray(g.sum(0), dtype=np.float64)
    return {"mass": float(rho.sum()), "phi_total": float(phi.sum()),
            "phi_min": float(phi.min()), "phi_max": float(phi.max()),
            "phi_var": float(phi.var()), "rho_min": float(rho.min())}
