"""``tdp.costmodel`` — the analytical performance model behind tuning.

The paper's portability claim rests on the abstraction exposing *enough
structure to reason about performance*: grid geometry, halo widths,
vector length, and the per-stage memory models are all part of the
:class:`~repro.core.api.LaunchPlan` / :class:`~repro.core.program.ProgramPlan`
surface.  This module turns that structure into numbers:

* :class:`MachineProfile` — per-device peak-FLOP / HBM-bandwidth /
  VMEM-size / link-bandwidth rates.  Calibrated once by a
  micro-benchmark (:func:`calibrate`) and cached on disk under
  ``results/tuning/machine-<device>[-interpret].json``
  (:func:`machine_profile`).  Interpreter rates are *honest*: an
  ``interpret=True`` profile is calibrated through actual Pallas
  interpret-mode launches and can never answer for a compiled run —
  :func:`predict` raises on the mismatch, mirroring the autotune
  cache-key rule that keeps interpreter medians out of compiled entries.

* :func:`predict` — a roofline predictor: per stage,
  ``t = max(flops / peak, hbm_bytes / bw · spill)`` with
  ``spill = max(1, vmem_bytes / profile.vmem_bytes)``, summed over the
  step, plus a communication term ``exchanged_bytes_per_step /
  link_bw`` driven by :meth:`CompiledProgram.comm_stats`.  FLOPs come
  from abstractly tracing the kernel body (:func:`kernel_flops`);
  bytes from the plan memory models.  The estimate reports seconds,
  the three time terms, and the binding bottleneck
  (``compute`` / ``hbm`` / ``vmem-spill`` / ``comm``).

* a second, XLA-derived backend (``source="hlo"``): the trip-count-
  exact HLO walker (:func:`analyze`, absorbed from the retired
  ``repro.launch.hlo_analysis``) runs over the compiled step's
  post-optimisation HLO text — exact dot FLOPs and fusion-aware HBM
  traffic, at the price of a compile.

:func:`repro.core.autotune.autotune` uses :func:`predict` to rank the
candidate space and measure only the top-K (``top_k=``); see the
"Cost model & predictor-guided tuning" section of docs/targetdp_api.md.

Pure-stdlib at import time: jax is imported lazily inside the functions
that trace or calibrate, so the HLO walker stays usable standalone
(``python -m repro.core.costmodel hlo.txt``).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Mapping

#: VMEM budget of an interpret-mode (CPU) launch: the Pallas interpreter
#: has no fast memory, so windowed plans are held to a fixed 16 MiB —
#: the scoped-VMEM default Mosaic applies to a kernel that names no limit.
INTERPRET_VMEM_LIMIT = 16 * 2 ** 20

__all__ = [
    "MachineProfile", "CostEstimate", "predict", "roofline_seconds",
    "kernel_flops", "calibrate", "machine_profile", "load_profile",
    "store_profile", "profile_path", "analyze", "parse_module",
    "collective_bytes", "dryrun_record_terms", "DEVICE_PEAKS",
    "INTERPRET_VMEM_LIMIT", "device_kind", "vmem_limit_bytes",
]


# ---------------------------------------------------------------------------
# machine profiles
# ---------------------------------------------------------------------------

#: Per-device rates, keyed by ``device_kind`` as JAX reports it
#: (``jax.devices()[0].device_kind``).  A kind missing here is an error,
#: never a default.
#:
#: * ``"TPU v5 lite"`` (TPU v5e): 197 TFLOP/s bf16, 16 GB of HBM at
#:   819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect — Google Cloud
#:   documentation, "TPU v5e".  128 MiB of VMEM per TensorCore — "How to
#:   Think About TPUs" (JAX scaling book), TPU specs table.
#:   ``vmem_limit`` is the scoped-VMEM limit a compiled Pallas kernel is
#:   given (``pltpu.CompilerParams(vmem_limit_bytes=...)``): 100 MiB,
#:   leaving 28 MiB to the compiler's internal scratch.  ``dcn_bw``
#:   (cross-pod) has no published per-chip figure and is assumed; only
#:   :func:`dryrun_record_terms` reads it.
#: * ``"cpu"``: a deliberately conservative laptop-class estimate for
#:   XLA's CPU backend; :func:`calibrate` replaces the two rates it can
#:   measure, and ``results/tuning/machine-cpu:*.json`` caches them.
DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": dict(peak_flops=197e12, hbm_bw=819e9, link_bw=200e9,
                        dcn_bw=25e9, hbm_bytes=16 * 10 ** 9, vmem_bytes=128 * 2 ** 20,
                        vmem_limit=100 * 2 ** 20),
    "cpu": dict(peak_flops=1e11, hbm_bw=2e10, link_bw=1e10,
                hbm_bytes=8 * 2 ** 30, vmem_bytes=INTERPRET_VMEM_LIMIT,
                vmem_limit=INTERPRET_VMEM_LIMIT),
}

#: Interpret-mode rates: the Pallas interpreter runs the kernel body per
#: site chunk in Python, so everything is derated to its throughput
#: (placeholders until :func:`calibrate` ``(interpret=True)`` measures).
_INTERPRET_RATES = dict(peak_flops=5e7, hbm_bw=5e8, link_bw=5e8,
                        hbm_bytes=8 * 2 ** 30,
                        vmem_bytes=INTERPRET_VMEM_LIMIT)


def device_kind() -> str:
    """``device_kind`` of the first device of the default backend."""
    import jax
    return jax.devices()[0].device_kind


def device_peaks(kind: str) -> dict[str, float]:
    """The :data:`DEVICE_PEAKS` row of ``kind``; unknown kinds raise."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"device kind {kind!r} has no row in costmodel.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)}); add its published peaks "
            f"and VMEM size with their source") from None


def vmem_limit_bytes(interpret: bool = False) -> int:
    """The VMEM budget of one Pallas launch: the scoped-VMEM limit a
    compiled kernel is given on this process's device (from
    :data:`DEVICE_PEAKS`), or :data:`INTERPRET_VMEM_LIMIT` in interpret
    mode.  The windowed executor passes it to Mosaic, and the plan-build
    guard and ``tdp.autotune``'s pruning hold plans to it."""
    if interpret:
        return INTERPRET_VMEM_LIMIT
    return int(device_peaks(device_kind())["vmem_limit"])


@dataclass(frozen=True)
class MachineProfile:
    """Per-device roofline rates.

    ``device`` is the autotune spelling ``"<platform>:<device_kind>"``;
    ``interpret`` marks a profile calibrated through the Pallas
    interpreter (orders of magnitude slower — never comparable to
    compiled rates, and :func:`predict` enforces that).  ``source``
    records provenance: ``"default"`` (table), ``"calibrated"``
    (micro-benchmark this process), ``"cached"`` (read back from disk).
    """

    device: str
    interpret: bool = False
    peak_flops: float = 1e11     # FLOP/s
    hbm_bw: float = 2e10         # bytes/s main-memory bandwidth
    vmem_bytes: int = INTERPRET_VMEM_LIMIT  # fast-memory capacity
    hbm_bytes: int = 8 * 2 ** 30           # main-memory capacity
    link_bw: float = 1e10        # bytes/s inter-device (ICI) link
    dcn_bw: float = 1e10         # bytes/s cross-pod link
    source: str = "default"

    @classmethod
    def default(cls, device: str | None = None,
                interpret: bool = False) -> "MachineProfile":
        """The table profile for ``device`` (``"<platform>:<kind>"``;
        the current device if None).  Interpret profiles take the
        interpreter rates; compiled ones the :data:`DEVICE_PEAKS` row of
        the kind, which must exist."""
        dev = device if device is not None else _device_kind()
        if interpret:
            rates = _INTERPRET_RATES
        else:
            rates = {k: v for k, v in
                     device_peaks(dev.split(":", 1)[-1]).items()
                     if k != "vmem_limit"}
        return cls(device=dev, interpret=bool(interpret), source="default",
                   **rates)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping) -> "MachineProfile":
        return cls(device=str(d["device"]),
                   interpret=bool(d.get("interpret", False)),
                   peak_flops=float(d["peak_flops"]),
                   hbm_bw=float(d["hbm_bw"]),
                   vmem_bytes=int(d["vmem_bytes"]),
                   hbm_bytes=int(d.get("hbm_bytes", 8 * 2 ** 30)),
                   link_bw=float(d.get("link_bw", 1e10)),
                   dcn_bw=float(d.get("dcn_bw", 1e10)),
                   source=str(d.get("source", "cached")))


def _device_kind() -> str:
    import jax
    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind}"


def _best_seconds(fn, reps: int = 5) -> float:
    """Best-of-``reps`` wall seconds of ``fn()`` (blocks on outputs)."""
    import time

    import jax
    jax.block_until_ready(fn())            # compile / warm
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _calibrate_compiled(reps: int) -> dict[str, float]:
    """Measured peak-FLOP and HBM rates through jitted XLA kernels."""
    import jax
    import jax.numpy as jnp

    n = 512                                 # 0.27 GFLOP matmul
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    t_mm = _best_seconds(lambda: mm(a, a), reps)
    peak = 2.0 * n ** 3 / max(t_mm, 1e-9)

    m = 4 * 2 ** 20                         # 16 MiB per operand stream
    x = jnp.ones((m,), jnp.float32)
    add = jax.jit(lambda u, v: u + v)
    t_add = _best_seconds(lambda: add(x, x), reps)
    bw = 3.0 * 4 * m / max(t_add, 1e-9)     # 2 reads + 1 write
    return {"peak_flops": peak, "hbm_bw": bw}


def _calibrate_interpret(reps: int) -> dict[str, float]:
    """Measured rates through actual Pallas interpret-mode launches —
    the honest interpreter numbers (the emulator is the bottleneck, not
    the hardware)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n = 1 << 14                             # tiny: the interpreter is slow

    def add_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    add = pl.pallas_call(
        add_kernel, out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=True)
    x = jnp.ones((n,), jnp.float32)
    t_add = _best_seconds(lambda: add(x, x), reps)
    bw = 3.0 * 4 * n / max(t_add, 1e-9)

    k = 8

    def fma_kernel(x_ref, o_ref):
        v = x_ref[...]
        acc = v
        for _ in range(k):
            acc = acc * v + v               # 2 FLOPs per element per rung
        o_ref[...] = acc

    fma = pl.pallas_call(
        fma_kernel, out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=True)
    t_fma = _best_seconds(lambda: fma(x), reps)
    peak = 2.0 * k * n / max(t_fma, 1e-9)
    return {"peak_flops": peak, "hbm_bw": bw}


def calibrate(device: str | None = None, interpret: bool = False, *,
              reps: int = 5) -> MachineProfile:
    """Micro-benchmark the current device into a :class:`MachineProfile`.

    Compiled profiles time a jitted matmul (peak FLOP/s) and a jitted
    streaming add (HBM bytes/s); ``interpret=True`` profiles time the
    same shapes through Pallas interpret-mode launches instead, so the
    recorded rates are the interpreter's, never the hardware's.  VMEM
    and link numbers are not measurable from a single host and keep
    their table defaults.  A micro-benchmark that cannot run raises."""
    base = MachineProfile.default(device, interpret)
    rates = (_calibrate_interpret(reps) if interpret
             else _calibrate_compiled(reps))
    return dataclasses.replace(base, source="calibrated", **rates)


# -- profile cache (results/tuning/machine-<device>[-interpret].json) -------

def profile_path(cache_dir: str, device: str, interpret: bool) -> str:
    dev = device.replace(" ", "_").replace("/", "_")
    tag = "-interpret" if interpret else ""
    return os.path.join(cache_dir, f"machine-{dev}{tag}.json")


def load_profile(cache_dir: str, device: str,
                 interpret: bool) -> MachineProfile | None:
    """The cached profile, or ``None`` on miss.  A corrupt file, a
    device mismatch, or an interpret-flag mismatch is a miss, never an
    error — the same contract as the autotune cache."""
    path = profile_path(cache_dir, device, interpret)
    try:
        with open(path) as fh:
            d = json.load(fh)
        if (str(d.get("device")) != device
                or bool(d.get("interpret", False)) != bool(interpret)):
            return None
        prof = MachineProfile.from_dict(d)
    except (OSError, ValueError, KeyError, TypeError):
        return None
    return dataclasses.replace(prof, source="cached")


def store_profile(cache_dir: str, profile: MachineProfile) -> str:
    """Atomically persist ``profile`` (tempfile + ``os.replace``, like
    the tuning cache — an interrupted write never truncates)."""
    os.makedirs(cache_dir, exist_ok=True)
    path = profile_path(cache_dir, profile.device, profile.interpret)
    fd, tmp = tempfile.mkstemp(dir=cache_dir, prefix=".machine-",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as fh:
            json.dump(profile.as_dict(), fh, indent=1)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


_PROFILE_MEMO: dict[tuple, MachineProfile] = {}


def machine_profile(device: str | None = None, interpret: bool = False, *,
                    cache_dir: str = "results/tuning",
                    calibrate_if_missing: bool = True,
                    store: bool = False,
                    force: bool = False) -> MachineProfile:
    """The one-stop profile lookup: in-process memo → on-disk cache →
    :func:`calibrate` → table default.

    ``store=True`` persists a freshly calibrated profile to
    ``cache_dir`` (the bench path does; :func:`predict`'s implicit
    lookup never writes).  ``force=True`` recalibrates, bypassing both
    caches."""
    dev = device if device is not None else _device_kind()
    memo_key = (dev, bool(interpret), cache_dir)
    if not force:
        hit = _PROFILE_MEMO.get(memo_key)
        if hit is not None:
            return hit
        cached = load_profile(cache_dir, dev, interpret)
        if cached is not None:
            _PROFILE_MEMO[memo_key] = cached
            return cached
    prof = (calibrate(dev, interpret) if calibrate_if_missing
            else MachineProfile.default(dev, interpret))
    if store and prof.source == "calibrated":
        store_profile(cache_dir, prof)
    _PROFILE_MEMO[memo_key] = prof
    return prof


# ---------------------------------------------------------------------------
# the estimate
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CostEstimate:
    """One prediction: seconds, the three roofline terms, the inputs
    they came from, and the binding bottleneck.

    ``bottleneck`` ∈ {``"compute"``, ``"hbm"``, ``"vmem-spill"``,
    ``"comm"``}; ``source`` ∈ {``"analytic"``, ``"hlo"``};
    ``per_stage`` holds one row per Program stage on aggregated
    estimates (empty for single launches)."""

    seconds: float
    t_compute: float
    t_hbm: float
    t_comm: float
    flops: float
    hbm_bytes: float
    vmem_bytes: float
    comm_bytes: float
    bottleneck: str
    source: str
    device: str
    per_stage: tuple = ()

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["per_stage"] = [dict(r) for r in self.per_stage]
        return d

    def __repr__(self):
        return (f"CostEstimate({self.seconds:.3g}s, "
                f"bottleneck={self.bottleneck!r}, source={self.source!r}, "
                f"flops={self.flops:.3g}, hbm={self.hbm_bytes:.3g}B, "
                f"comm={self.comm_bytes:.3g}B)")


def roofline_seconds(flops: float, hbm_bytes: float, *,
                     vmem_bytes: float = 0.0, comm_bytes: float = 0.0,
                     profile: MachineProfile,
                     source: str = "analytic") -> CostEstimate:
    """The pure roofline: ``max(flops/peak, hbm/bw · spill) + comm/link``.

    ``spill = max(1, vmem_bytes / profile.vmem_bytes)`` derates the HBM
    term when the working set exceeds fast memory (every spilled window
    makes an extra round trip).  Monotone non-decreasing in every one of
    ``flops``, ``hbm_bytes``, ``vmem_bytes``, ``comm_bytes`` by
    construction — the property the model tests pin."""
    t_c = float(flops) / profile.peak_flops
    spill = (max(1.0, float(vmem_bytes) / profile.vmem_bytes)
             if profile.vmem_bytes else 1.0)
    t_h = (float(hbm_bytes) / profile.hbm_bw) * spill
    t_x = float(comm_bytes) / profile.link_bw
    seconds = max(t_c, t_h) + t_x
    if t_x > max(t_c, t_h):
        bottleneck = "comm"
    elif t_c >= t_h:
        bottleneck = "compute"
    else:
        bottleneck = "vmem-spill" if spill > 1.0 else "hbm"
    return CostEstimate(
        seconds=seconds, t_compute=t_c, t_hbm=t_h, t_comm=t_x,
        flops=float(flops), hbm_bytes=float(hbm_bytes),
        vmem_bytes=float(vmem_bytes), comm_bytes=float(comm_bytes),
        bottleneck=bottleneck, source=source, device=profile.device)


# ---------------------------------------------------------------------------
# analytic FLOP counting (trace the kernel body abstractly)
# ---------------------------------------------------------------------------

#: FLOPs per output element for elementwise primitives.  Transcendentals
#: are charged a conventional 8 (polynomial approximation); pure data
#: movement (broadcast/transpose/slice/convert/...) is free.
_ELEMWISE_FLOPS = {
    "add": 1, "sub": 1, "mul": 1, "div": 2, "neg": 1, "max": 1, "min": 1,
    "abs": 1, "sign": 1, "floor": 1, "ceil": 1, "round": 1, "rem": 2,
    "integer_pow": 1, "square": 1, "clamp": 2, "select_n": 1,
    "eq": 1, "ne": 1, "lt": 1, "le": 1, "gt": 1, "ge": 1,
    "and": 1, "or": 1, "not": 1, "xor": 1,
    "exp": 8, "log": 8, "log1p": 8, "expm1": 8, "tanh": 8, "logistic": 8,
    "sin": 8, "cos": 8, "tan": 8, "atan2": 8, "pow": 8,
    "sqrt": 4, "rsqrt": 4, "cbrt": 8, "erf": 8, "erfc": 8, "erf_inv": 8,
}


def _aval_size(v) -> int:
    shape = getattr(getattr(v, "aval", None), "shape", ())
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _sub_jaxprs(val) -> list:
    out = []

    def visit(v):
        inner = getattr(v, "jaxpr", None)       # ClosedJaxpr
        if inner is not None and hasattr(inner, "eqns"):
            out.append(inner)
        elif hasattr(v, "eqns"):                # raw Jaxpr
            out.append(v)
        elif isinstance(v, (tuple, list)):
            for x in v:
                visit(x)

    visit(val)
    return out


def _jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        sub = 0.0
        for pval in eqn.params.values():
            for j in _sub_jaxprs(pval):
                sub += _jaxpr_flops(j)
        if sub:
            mult = (int(eqn.params.get("length", 1))
                    if prim == "scan" else 1)
            total += sub * mult
            continue
        if prim == "dot_general":
            out = _aval_size(eqn.outvars[0])
            contracting = eqn.params["dimension_numbers"][0][0]
            lhs_shape = eqn.invars[0].aval.shape
            k = 1
            for d in contracting:
                k *= int(lhs_shape[d])
            total += 2.0 * out * k
        elif prim in _ELEMWISE_FLOPS:
            total += (_ELEMWISE_FLOPS[prim]
                      * max(_aval_size(v) for v in eqn.outvars))
        elif prim.startswith(("reduce_", "cum", "arg")):
            total += max((_aval_size(v) for v in eqn.invars), default=0)
    return total


def kernel_flops(plan) -> float:
    """Arithmetic FLOPs of one launch of ``plan``, from an abstract
    trace of the kernel body.

    The body is traced once over one VVL chunk — stencil fields as
    ``(noffsets, ncomp, VVL)``, pointwise fields as ``(ncomp, VVL)``,
    the site index as ``(VVL,)`` int32, consts closed over — exactly the
    executor calling convention, then scaled by ``nsites / VVL``.
    Returns 0.0 when the trace is impossible (no shape metadata, kernel
    refuses abstract values): the prediction degrades to memory-bound,
    which is the right prior for lattice kernels."""
    if plan.shape is None or plan.field_ncomp is None:
        return 0.0
    try:
        import jax
        import jax.numpy as jnp
        vvl = int(plan.vvl)
        stencils = plan.stencils or (None,) * len(plan.field_ncomp)
        args = []
        for c, s in zip(plan.field_ncomp, stencils):
            c = int(c or 1)
            shape = (c, vvl) if s is None else (int(s.noffsets), c, vvl)
            args.append(jax.ShapeDtypeStruct(shape, jnp.float32))
        if plan.with_site_index:
            args.append(jax.ShapeDtypeStruct((vvl,), jnp.int32))
        body = (functools.partial(plan.kernel, **plan.consts)
                if plan.consts else plan.kernel)
        closed = jax.make_jaxpr(lambda *a: body(*a))(*args)
        per_chunk = _jaxpr_flops(closed.jaxpr)
    except Exception:
        return 0.0
    nsites = 1
    for s in plan.shape:
        nsites *= int(s)
    return per_chunk * (nsites / max(1, vvl))


# ---------------------------------------------------------------------------
# predict
# ---------------------------------------------------------------------------

def _resolve_profile(profile: MachineProfile | None,
                     interpret: bool) -> MachineProfile:
    if profile is not None:
        if bool(profile.interpret) != bool(interpret):
            raise ValueError(
                f"MachineProfile(interpret={profile.interpret}) cannot "
                f"answer for a plan with interpret={interpret} — "
                f"interpreter rates and compiled rates are never "
                f"comparable (calibrate both; see machine_profile())")
        return profile
    return machine_profile(interpret=interpret)


def _predict_stages(name, stages, profile, comm, itemsize,
                    source="analytic") -> CostEstimate:
    comm_bytes = float((comm or {}).get("exchanged_bytes_per_step", 0))
    rows = []
    t_c = t_h = flops = hbm = 0.0
    vmem = 0.0
    spilled = False
    for sname, p in stages:
        est = roofline_seconds(
            kernel_flops(p), p.hbm_bytes_estimate(itemsize),
            vmem_bytes=p.vmem_bytes_estimate(itemsize), profile=profile,
            source=source)
        rows.append({
            "stage": sname, "executor": p.target.executor,
            "wants": p.wants, "seconds": est.seconds,
            "t_compute": est.t_compute, "t_hbm": est.t_hbm,
            "flops": est.flops, "hbm_bytes": est.hbm_bytes,
            "vmem_bytes": est.vmem_bytes, "bottleneck": est.bottleneck})
        t_c += est.t_compute
        t_h += est.t_hbm
        flops += est.flops
        hbm += est.hbm_bytes
        vmem = max(vmem, est.vmem_bytes)
        spilled = spilled or est.bottleneck == "vmem-spill"
    t_x = comm_bytes / profile.link_bw
    seconds = sum(r["seconds"] for r in rows) + t_x
    if t_x > max(t_c, t_h):
        bottleneck = "comm"
    elif t_c >= t_h:
        bottleneck = "compute"
    else:
        bottleneck = "vmem-spill" if spilled else "hbm"
    return CostEstimate(
        seconds=seconds, t_compute=t_c, t_hbm=t_h, t_comm=t_x,
        flops=flops, hbm_bytes=hbm, vmem_bytes=vmem,
        comm_bytes=comm_bytes, bottleneck=bottleneck, source=source,
        device=profile.device, per_stage=tuple(rows))


def _predict_hlo(exe, profile, comm, itemsize) -> CostEstimate:
    """The XLA-derived backend: compile the step, walk the HLO."""
    import jax
    import jax.numpy as jnp
    if exe.dyn_names:
        raise ValueError("source='hlo' does not support programs with "
                         "BatchedConst parameters")
    args = [jax.ShapeDtypeStruct(
        (int(exe.program.ncomp[f] or 1), *exe.grid_shape), jnp.float32)
        for f in exe.program.fields]
    text = exe._jit_step.lower(*args).compile().as_text()
    ha = analyze(text)
    comm_bytes = float((comm or {}).get("exchanged_bytes_per_step", 0))
    comm_bytes = max(comm_bytes,
                     ha["wire_bytes_ici"] + ha["wire_bytes_dcn"])
    interp = any(p.interpret for _, p in exe.plan().stages)
    est = roofline_seconds(
        ha["flops"], ha["traffic_bytes"], comm_bytes=comm_bytes,
        profile=profile, source="hlo")
    row = {"stage": "<step>", "executor": exe.target.executor,
           "wants": "-", "seconds": est.seconds,
           "t_compute": est.t_compute, "t_hbm": est.t_hbm,
           "flops": est.flops, "hbm_bytes": est.hbm_bytes,
           "vmem_bytes": 0.0, "bottleneck": est.bottleneck,
           "interpret": interp}
    return dataclasses.replace(est, per_stage=(row,))


def predict(subject, target=None, profile: MachineProfile | None = None, *,
            grid_shape=None, source: str = "analytic", comm=None,
            itemsize: int = 4) -> CostEstimate:
    """Predict the per-step cost of ``subject``.

    Args:
      subject: a :class:`~repro.core.api.LaunchPlan`,
        :class:`~repro.core.program.ProgramPlan`,
        :class:`~repro.core.program.Program` (needs ``grid_shape``; the
        plan is built with ``target``), or
        :class:`~repro.core.program.CompiledProgram` (its own plan,
        target and :meth:`comm_stats` are used).
      target: the target to plan a bare ``Program`` under.
      profile: the :class:`MachineProfile`; defaults to
        :func:`machine_profile` for the subject's interpret mode.
        Passing a profile whose ``interpret`` flag mismatches the
        subject raises — interpreter numbers never answer for compiled
        runs, and vice versa.
      grid_shape: required for a bare ``Program``.
      source: ``"analytic"`` (plan memory models + traced-kernel FLOPs;
        no compilation) or ``"hlo"`` (compile and walk the
        post-optimisation HLO — trip-count-exact dots and fusion-aware
        traffic; ``CompiledProgram`` only).
      comm: override the communication stats dict (any mapping with
        ``exchanged_bytes_per_step``); defaults to the subject's
        :meth:`comm_stats` when it has one, else no comm term.
      itemsize: bytes per field element (float32 default).
    """
    from .api import LaunchPlan
    from .program import CompiledProgram, Program, ProgramPlan

    if source not in ("analytic", "hlo"):
        raise ValueError(f"source must be 'analytic' or 'hlo', "
                         f"got {source!r}")

    if isinstance(subject, CompiledProgram):
        if comm is None:
            comm = subject.comm_stats(itemsize)
        pplan = subject.plan()
        interp = any(p.interpret for _, p in pplan.stages)
        prof = _resolve_profile(profile, interp)
        if source == "hlo":
            return _predict_hlo(subject, prof, comm, itemsize)
        return _predict_stages(pplan.name, pplan.stages, prof, comm,
                               itemsize)
    if source == "hlo":
        raise ValueError("source='hlo' needs a CompiledProgram (the HLO "
                         "walker runs over a compiled step)")
    if isinstance(subject, Program):
        if grid_shape is None:
            raise ValueError("predict over a Program needs grid_shape")
        pplan = subject.plan(target, grid_shape=grid_shape)
        subject = pplan
    if isinstance(subject, ProgramPlan):
        interp = any(p.interpret for _, p in subject.stages)
        prof = _resolve_profile(profile, interp)
        return _predict_stages(subject.name, subject.stages, prof, comm,
                               itemsize)
    if isinstance(subject, LaunchPlan):
        prof = _resolve_profile(profile, subject.interpret)
        return _predict_stages(subject.name,
                               ((subject.name, subject),), prof, comm,
                               itemsize)
    raise TypeError(f"predict expects a LaunchPlan, ProgramPlan, Program "
                    f"or CompiledProgram; got {type(subject).__name__}")


# ---------------------------------------------------------------------------
# the XLA-derived backend: trip-count-exact HLO analysis
# (absorbed from the retired repro.launch.hlo_analysis)
# ---------------------------------------------------------------------------
#
# Why not ``compiled.cost_analysis()``: XLA's HloCostAnalysis visits each
# ``while`` body **once**, so anything under ``lax.scan`` (layer stacks,
# grad-accumulation, chunked attention) is undercounted by its trip count.
# The compiled HLO text, however, carries
# ``backend_config={"known_trip_count":{"n":...}}`` on every scan-derived
# while loop, so an exact account is a parse away:
#
#   1. split the module into computations; index every instruction's
#      output shape(s) by name;
#   2. build the call graph (while body/condition, fusion ``calls``,
#      ``to_apply``, conditional branches) and propagate a *multiplier* =
#      Σ over call sites of (caller multiplier × trip count);
#   3. FLOPs: every ``dot`` = 2 · prod(output) · K (K = lhs contracting
#      extents) × multiplier;
#   4. HBM traffic: Σ (operand bytes + output bytes) over instructions in
#      non-fusion computations × multiplier (a fusion is one kernel: its
#      internals live in registers/VMEM; its call site counts);
#   5. collectives: operand bytes × multiplier, plus a per-chip
#      *wire-byte* estimate from ring algorithms (see ``_WIRE``); groups
#      are classified ICI vs DCN by their device stride (``pod_stride``).
#
# All shapes in a post-partitioning module are per-chip shard shapes, so
# every number is per-chip.

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "iota"}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_INSTR_RE = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(\([^=]*?\)|\w+\[[0-9,]*\](?:\{[^}]*\})?)"
    r"\s+([\w\-]+)\(")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\]"
                        r"(?:T\(([0-9,]+)\))?")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _shape_list(text: str) -> list[tuple[str, tuple[int, ...]]]:
    """All dtype[dims] shapes in a type string (handles tuples)."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        out.append((m.group(1), dims))
    return out


def _nbytes(shapes) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


@dataclass
class Instr:
    name: str
    out_shapes: list
    opcode: str
    operands: list
    line: str


@dataclass
class Computation:
    name: str
    instrs: dict = field(default_factory=dict)
    order: list = field(default_factory=list)


def parse_module(text: str):
    comps: dict[str, Computation] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        if cur is None:
            # computation headers sit at column 0:
            #   %name (args...) -> type {     /  ENTRY %name (...) -> ... {
            if (line.startswith("%") or line.startswith("ENTRY")) and \
                    line.rstrip().endswith("{") and "->" in line:
                is_entry = line.startswith("ENTRY")
                tok = line.split()[1] if is_entry else line.split()[0]
                cur = Computation(tok.lstrip("%"))
                comps[cur.name] = cur
                if is_entry:
                    entry = cur.name
            continue
        if line.startswith("}"):
            cur = None
            continue
        ins = _parse_instr(line)
        if ins is not None:
            cur.instrs[ins.name] = ins
            cur.order.append(ins.name)
    return comps, entry


def _parse_instr(line: str):
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if not s.startswith("%") and not s[:1].isalpha():
        return None
    eq = s.find(" = ")
    if eq < 0:
        return None
    name = s[:eq].lstrip("%")
    rest = s[eq + 3:]
    # type: either a balanced-paren tuple (may contain /*index=N*/ comments)
    # or dtype[dims]{layout}
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        typ, rest2 = rest[:i + 1], rest[i + 1:]
    else:
        m = re.match(r"\w+\[[0-9,]*\](?:\{[^}]*\})?", rest)
        if not m:
            return None
        typ, rest2 = m.group(0), rest[m.end():]
    rest2 = rest2.lstrip()
    mo = re.match(r"([\w\-]+)\(", rest2)
    if not mo:
        return None
    opcode = mo.group(1)
    paren = rest2.find("(", mo.start())
    depth = 0
    for i in range(paren, len(rest2)):
        if rest2[i] == "(":
            depth += 1
        elif rest2[i] == ")":
            depth -= 1
            if depth == 0:
                break
    operands = _OPERAND_RE.findall(rest2[paren:i + 1])
    return Instr(name, _shape_list(typ), opcode, operands, line)


def _call_edges(comp: Computation):
    """[(callee_name, factor, kind)] for one computation."""
    edges = []
    for iname in comp.order:
        ins = comp.instrs[iname]
        line = ins.line
        if ins.opcode == "while":
            trip = 1
            mt = _TRIP_RE.search(line)
            if mt:
                trip = int(mt.group(1))
            for key in ("body=", "condition="):
                k = line.find(key)
                if k >= 0:
                    nm = re.match(r"%?([\w.\-]+)", line[k + len(key):].lstrip("%"))
                    if nm:
                        edges.append((nm.group(1), trip,
                                      "while_" + key[:-1]))
        else:
            for key, kind in (("calls=", "fusion"), ("to_apply=", "apply"),
                              ("branch_computations={", "cond"),
                              ("body=", "body"), ("condition=", "condition")):
                k = line.find(key)
                if k < 0:
                    continue
                tail = line[k + len(key):]
                if key.endswith("{"):
                    names = re.findall(r"%([\w.\-]+)", tail[:tail.find("}")])
                    for nm in names:
                        edges.append((nm, 1, kind))
                else:
                    nm = re.match(r"%?([\w.\-]+)", tail.lstrip("%"))
                    if nm:
                        edges.append((nm.group(1), 1, kind))
    return edges


def _multipliers(comps, entry):
    mult = defaultdict(float)
    mult[entry] = 1.0
    # topological: repeatedly relax (call graph is a DAG in HLO)
    edges = {c: _call_edges(comp) for c, comp in comps.items()}
    order = []
    seen = set()

    def dfs(c):
        if c in seen:
            return
        seen.add(c)
        for callee, _, _ in edges.get(c, ()):  # post-order
            dfs(callee)
        order.append(c)

    dfs(entry)
    for c in reversed(order):                  # callers before callees
        for callee, factor, _ in edges.get(c, ()):
            mult[callee] += mult[c] * factor
    fusion_like = {callee for c in comps for callee, _, kind in edges[c]
                   if kind in ("fusion", "apply")}
    return mult, fusion_like


def _dot_flops(ins: Instr, comp: Computation) -> float:
    out_elems = 1
    for _, dims in ins.out_shapes:
        for d in dims:
            out_elems *= d
    k = 1
    mc = _CONTRACT_RE.search(ins.line)
    if mc and ins.operands:
        lhs = comp.instrs.get(ins.operands[0])
        if lhs is not None and lhs.out_shapes:
            shape = lhs.out_shapes[0][1]
            for idx in (int(i) for i in mc.group(1).split(",") if i):
                if idx < len(shape):
                    k *= shape[idx]
    return 2.0 * out_elems * k


def _group_size_and_kind(line: str, pod_stride: int = 256):
    """(group_size, dcn_fraction).

    A group *spans* pods when its member span (stride·(size−1)) reaches
    the pod stride; a ring over such a group crosses the DCN boundary
    ``span // pod_stride`` times out of ``size−1`` hops — that fraction
    of the wire bytes rides DCN, the rest ICI.  Pure-pod groups (stride
    = pod_stride) give fraction 1."""
    def frac(stride, gsize):
        if gsize <= 1:
            return 0.0
        span = stride * (gsize - 1)
        crossings = span // pod_stride
        return min(1.0, crossings / (gsize - 1))

    m = _GROUPS_RE.search(line)
    if m:
        iota = [int(x) for x in m.group(3).split(",")]
        gsize = int(m.group(2))
        # transposed iota ⇒ group members stride by the trailing iota dims
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            strides = 1
            for d in perm[1:]:
                strides *= iota[d]
            stride = strides
        else:
            stride = 1
        return gsize, frac(stride, gsize)
    m2 = _GROUPS_LIST_RE.search(line)
    if m2:
        members = [int(x) for x in m2.group(1).split(",")]
        gsize = len(members)
        stride = abs(members[1] - members[0]) if gsize > 1 else 1
        return gsize, frac(stride, gsize)
    return 1, 0.0


def _operand_nbytes(ins: Instr, comp: Computation, idx: int) -> int:
    if idx >= len(ins.operands):
        return 0
    o = comp.instrs.get(ins.operands[idx])
    return _nbytes(o.out_shapes) if o is not None else 0


def _fusion_param_read(callee: Computation, pidx: int, full: int) -> int:
    """Bytes a fusion actually reads of parameter ``pidx``.

    If every consumer of the parameter inside the fusion is a windowed
    read (dynamic-slice / slice / gather), charge the windows, not the
    whole tensor — scan bodies dynamic-slice one layer out of the stacked
    parameters *inside* a fusion, and charging the stack per iteration is
    a ~10× traffic overcount.
    """
    pname = None
    consumers = []
    for iname in callee.order:
        ins = callee.instrs[iname]
        if ins.opcode == "parameter" and ins.line.strip().split(" = ")[0] \
                .lstrip("%").startswith(f"param_{pidx}"):
            pname = ins.name
            break
    if pname is None:
        # fall back: parameters are in order
        params = [i for i in callee.order
                  if callee.instrs[i].opcode == "parameter"]
        if pidx < len(params):
            pname = params[pidx]
    if pname is None:
        return full
    windowed = 0
    for iname in callee.order:
        ins = callee.instrs[iname]
        if pname in ins.operands:
            consumers.append(ins)
    if not consumers:
        return 0
    for ins in consumers:
        if ins.opcode in ("dynamic-slice", "slice", "gather"):
            windowed += _nbytes(ins.out_shapes)
        elif ins.opcode == "dynamic-update-slice" and \
                ins.operands and ins.operands[0] == pname:
            windowed += _operand_nbytes(ins, callee, 1)  # aliased update
        else:
            return full
    return windowed


def _read_bytes(ins: Instr, comp: Computation, out_bytes: int,
                comps=None) -> int:
    """Bytes actually *read* by an instruction.

    Sliced/gathered reads touch only the addressed window, not the whole
    operand.  In-place updates (dynamic-update-slice / scatter) read+write
    only the update window; XLA aliases the rest.  Fusion call sites defer
    to :func:`_fusion_param_read` per operand.
    """
    op = ins.opcode
    if op in ("dynamic-slice", "slice", "gather"):
        return out_bytes
    if op == "dynamic-update-slice":
        return _operand_nbytes(ins, comp, 1)         # the update window
    if op == "scatter":
        return (_operand_nbytes(ins, comp, 1) +      # indices
                2 * _operand_nbytes(ins, comp, 2))   # updates read+write
    if op == "fusion" and comps is not None:
        mcall = re.search(r"calls=%?([\w.\-]+)", ins.line)
        callee = comps.get(mcall.group(1)) if mcall else None
        if callee is not None:
            total = 0
            for i in range(len(ins.operands)):
                total += _fusion_param_read(callee, i,
                                            _operand_nbytes(ins, comp, i))
            return total
    total = 0
    for i in range(len(ins.operands)):
        total += _operand_nbytes(ins, comp, i)
    return total


#: per-chip ring-algorithm wire bytes per collective (b = operand bytes,
#: s = replica-group size)
_WIRE = {
    "all-gather": lambda b, s: b * (s - 1),
    "reduce-scatter": lambda b, s: b * (s - 1) / s,
    "all-reduce": lambda b, s: 2 * b * (s - 1) / s,
    "all-to-all": lambda b, s: b * (s - 1) / s,
    "collective-permute": lambda b, s: b,
}


def analyze(text: str, *, pod_stride: int = 256) -> dict:
    comps, entry = parse_module(text)
    if entry is None:
        raise ValueError("no ENTRY computation found")
    mult, fusion_like = _multipliers(comps, entry)

    flops = 0.0
    traffic = 0.0
    coll = {op: {"operand_bytes": 0.0, "wire_bytes_ici": 0.0,
                 "wire_bytes_dcn": 0.0, "count": 0} for op in _COLLECTIVES}

    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        in_fusion = cname in fusion_like
        for iname in comp.order:
            ins = comp.instrs[iname]
            op = ins.opcode
            base = op[:-6] if op.endswith("-start") else op
            if op == "dot":
                flops += m * _dot_flops(ins, comp)
            if in_fusion:
                continue                      # fused internals: no traffic
            if op.endswith("-done") or op in _FREE_OPS or op == "while":
                continue
            out_bytes = _nbytes(ins.out_shapes)
            if op == "dynamic-update-slice":       # in-place: writes window
                out_bytes = _operand_nbytes(ins, comp, 1)
            elif op == "scatter":
                out_bytes = 0                      # counted in _read_bytes
            operand_bytes = _read_bytes(ins, comp, out_bytes, comps)
            traffic += m * (operand_bytes + out_bytes)
            if base in _COLLECTIVES:
                gsize, dcn_frac = _group_size_and_kind(ins.line, pod_stride)
                c = coll[base]
                c["operand_bytes"] += m * operand_bytes
                wire = m * _WIRE[base](operand_bytes, max(gsize, 1))
                c["wire_bytes_dcn"] += wire * dcn_frac
                c["wire_bytes_ici"] += wire * (1.0 - dcn_frac)
                c["count"] += m
    total_ici = sum(c["wire_bytes_ici"] for c in coll.values())
    total_dcn = sum(c["wire_bytes_dcn"] for c in coll.values())
    return {
        "flops": flops,
        "traffic_bytes": traffic,
        "collectives": coll,
        "wire_bytes_ici": total_ici,
        "wire_bytes_dcn": total_dcn,
        "n_computations": len(comps),
    }


def collective_bytes(hlo_text: str) -> dict:
    """Per-opcode summed *operand* bytes (post-partitioning = per chip).

    Start ops (``all-reduce-start``) are counted; their matching
    ``-done`` ops carry no payload.  ``collective-permute`` pairs count
    once.  (The quick line-scan companion to :func:`analyze` — no call
    graph, no multipliers; absorbed from the retired dryrun module.)
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for op in _COLLECTIVES:
            marker = f" {op}("
            start_marker = f" {op}-start("
            pos = line.find(marker)
            if pos < 0:
                pos = line.find(start_marker)
            if pos < 0:
                continue
            paren = line.find("(", pos)
            operands = line[paren:line.find(")", paren) + 1]
            b = sum(_nbytes([(m.group(1), tuple(
                int(d) for d in m.group(2).split(",") if d))])
                for m in _SHAPE_RE.finditer(operands))
            out[op] += b
            counts[op] += 1
            break
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def dryrun_record_terms(rec: Mapping, profile: MachineProfile | None = None
                        ) -> dict:
    """Roofline terms for one ``results/dryrun`` record.  ``profile``
    defaults to the TPU table profile the dry-run targets."""
    p = (profile if profile is not None
         else MachineProfile.default("tpu:TPU v5 lite"))
    ha = rec["hlo_analysis"]
    t_c = ha["flops"] / p.peak_flops
    t_m = ha["traffic_bytes"] / p.hbm_bw
    t_x = (ha["wire_bytes_ici"] / p.link_bw
           + ha["wire_bytes_dcn"] / p.dcn_bw)
    chips = rec["n_devices"]
    hlo_total = ha["flops"] * chips
    useful = rec["model_flops"] / hlo_total if hlo_total else 0.0
    mem = rec["memory_analysis"]
    per_dev = (mem.get("argument_size_in_bytes", 0) +
               mem.get("temp_size_in_bytes", 0))
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    total = t_c + t_m + t_x
    return {
        "t_compute": t_c, "t_memory": t_m, "t_collective": t_x,
        "dominant": dom[0], "t_dominant": dom[1],
        "frac": dom[1] / total if total else 0.0,
        "useful_ratio": useful,
        "bytes_per_dev": per_dev,
        "fits": per_dev <= p.hbm_bytes,
    }


if __name__ == "__main__":
    import sys
    with open(sys.argv[1]) as f:
        print(json.dumps(analyze(f.read()), indent=1))
