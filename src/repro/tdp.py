"""The paper-facing targetDP API surface: ``from repro import tdp``.

One kernel body, one launch syntax, retargeted by swapping the
:class:`Target` descriptor — the paper's single-source contract as a
module namespace::

    from repro import tdp

    @tdp.kernel(fields=[tdp.field(3)], out=3)
    def scale(x, a=1.0):
        return a * x

    y = tdp.launch(scale, tdp.Target("pallas", vvl=256), x, a=2.0)

Paper macro → API mapping (full table in docs/targetdp_api.md):

==================  =====================================================
paper               here
==================  =====================================================
``TARGET_ENTRY``    ``@tdp.kernel`` (or :func:`site_kernel` legacy form)
``TARGET_LAUNCH``   :func:`tdp.launch` — ``launch(spec, target, *arrays)``
``TARGET_TLP``      the executor's chunk loop (vmap / pallas grid)
``TARGET_ILP``      the trailing VVL axis, ``Target.vvl`` tunes it
``VVL`` AoSoA site  ``Target.layout="aosoa"`` — executor-internal
ordering            SoA↔AoSoA transforms at field boundaries
                    (:mod:`repro.core.layout`), ``vvl`` as the inner
                    block width; bit-identical across layouts
``TARGET_CONST``    :class:`TargetConst` / launch ``**consts``
C-vs-CUDA switch    :class:`Target` + :func:`register_executor`
host step glue      :func:`tdp.program` — multi-launch step graphs with
                    double-buffered fields and one halo schedule per
                    step (:mod:`repro.core.program`)
per-device tuning   :func:`tdp.autotune` — measured selection over
                    ``Target.tuning`` / the executor axis, cached on
                    disk per (program, grid, device)
ensemble serving    ``compiled.vmap(batch)`` / :class:`FleetDriver` —
                    batched trajectories behind submit/poll/stream,
                    ``BatchedConst`` parameter sweeps, durable tickets
                    (:mod:`repro.core.fleet`)
failure handling    :class:`HealthPolicy` guards (``run(...,
                    health=...)``), ticket status/retry/rollback on the
                    driver, :mod:`tdp.faults <repro.core.faults>` chaos
                    injectors (:mod:`repro.core.health`)
==================  =====================================================
"""
from repro.core.target import (  # noqa: F401
    Target,
    as_target,
    default_vvl,
    set_default_vvl,
)
from repro.core.spec import (  # noqa: F401
    FieldSpec,
    KernelSpec,
    field,
    kernel,
)
from repro.core.registry import (  # noqa: F401
    compatible_executors,
    executor_tunables,
    executor_wants,
    get_executor,
    get_executor_entry,
    list_executors,
    register_executor,
    registry_version,
    unregister_executor,
)
from repro.core.api import (  # noqa: F401
    LaunchPlan,
    WindowShapeError,
    WindowVmemError,
    gather_neighbors,
    halo_extend,
    launch,
    launch_plan,
    pad_sites,
    xla_executor,
)
from repro.core.layout import (  # noqa: F401
    LAYOUTS,
    aosoa_nblocks,
    aosoa_to_soa,
    soa_to_aosoa,
)
from repro.core.program import (  # noqa: F401
    CompiledProgram,
    Program,
    ProgramPlan,
    exchange_ghosts,
    exchange_stats,
    Stage,
    program,
    stage,
)
from repro.core.autotune import (  # noqa: F401
    Candidate,
    TuneReport,
    TuneResult,
    autotune,
    default_space,
    plane_block_candidates,
    wall_clock_timer,
)
from repro.core import costmodel  # noqa: F401  (module: tdp.costmodel)
from repro.core.costmodel import (  # noqa: F401
    CostEstimate,
    MachineProfile,
    machine_profile,
    predict,
    roofline_seconds,
)
from repro.core.execute import reduce, site_kernel  # noqa: F401
from repro.core.lattice import (  # noqa: F401
    D3Q19_VELOCITIES,
    Lattice,
    Stencil,
    STENCIL_D3Q19_PULL,
    STENCIL_GRAD_6PT,
    STENCIL_GRAD_19PT,
    token_lattice,
)
from repro.core import fleet  # noqa: F401  (module: tdp.fleet)
from repro.core.fleet import (  # noqa: F401
    FleetDriver,
    FleetProgram,
    Ticket,
)
from repro.core import faults, health  # noqa: F401  (tdp.faults, tdp.health)
from repro.core.faults import InjectedFault  # noqa: F401
from repro.core.health import (  # noqa: F401
    Diagnosis,
    HealthError,
    HealthPolicy,
)
from repro.core.state import (  # noqa: F401
    FIELD_ATOL,
    ProgramState,
    check_fields,
    validate_field,
)
from repro.core.memory import (  # noqa: F401
    BatchedConst,
    TargetConst,
    copy_constant_to_target,
    copy_from_target,
    copy_to_target,
    sync_target,
    target_free,
    target_malloc,
)

__all__ = [
    "Target", "as_target", "default_vvl", "set_default_vvl",
    "FieldSpec", "KernelSpec", "field", "kernel",
    "register_executor", "unregister_executor", "get_executor",
    "get_executor_entry", "executor_wants", "list_executors",
    "registry_version",
    "launch", "launch_plan", "LaunchPlan", "xla_executor",
    "gather_neighbors", "halo_extend", "pad_sites", "WindowShapeError",
    "WindowVmemError",
    "LAYOUTS", "aosoa_nblocks", "aosoa_to_soa", "soa_to_aosoa",
    "Program", "CompiledProgram", "ProgramPlan", "Stage", "program",
    "exchange_ghosts", "exchange_stats",
    "stage",
    "autotune", "default_space", "plane_block_candidates",
    "Candidate", "TuneReport", "TuneResult", "wall_clock_timer",
    "costmodel", "CostEstimate", "MachineProfile", "machine_profile",
    "predict", "roofline_seconds",
    "compatible_executors", "executor_tunables",
    "reduce", "site_kernel",
    "Lattice", "token_lattice", "Stencil", "D3Q19_VELOCITIES",
    "STENCIL_D3Q19_PULL", "STENCIL_GRAD_6PT", "STENCIL_GRAD_19PT",
    "TargetConst", "copy_constant_to_target", "copy_to_target",
    "copy_from_target", "sync_target", "target_free", "target_malloc",
    "fleet", "FleetProgram", "FleetDriver", "Ticket",
    "ProgramState", "BatchedConst", "validate_field",
    "check_fields", "FIELD_ATOL",
    "health", "faults", "HealthPolicy", "HealthError", "Diagnosis",
    "InjectedFault",
]
