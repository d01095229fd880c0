"""targetDP core — the paper's contribution as a composable JAX module.

Public surface (paper → here):

* lattice/fields: :class:`Lattice`, :class:`Field` (SoA mandated, AoS kept
  as the measurable baseline layout), :class:`Stencil` neighbourhoods.
* memory model: :func:`target_malloc`, :func:`copy_to_target`,
  :func:`copy_from_target`, masked variants, :class:`TargetConst`,
  :func:`sync_target`.
* execution model (declarative): :class:`KernelSpec` + :func:`kernel`
  (``TARGET_ENTRY`` with declared field roles), :class:`Target` (the
  build switch as an exchangeable descriptor), :func:`tdp_launch`
  (``TARGET_LAUNCH`` + ``TARGET_TLP``/``TARGET_ILP`` with tunable VVL)
  dispatching through :func:`register_executor`'s table, and
  :func:`reduce` (the paper's §V planned extension).
* legacy surface: :func:`site_kernel`, :func:`launch`,
  :func:`launch_stencil` (deprecation shims over ``tdp_launch``).

The ergonomic import is ``from repro import tdp`` — see
:mod:`repro.tdp` and docs/targetdp_api.md.
"""
from .lattice import (
    D3Q19_VELOCITIES,
    Lattice,
    Stencil,
    STENCIL_D3Q19_PULL,
    STENCIL_GRAD_6PT,
    STENCIL_GRAD_19PT,
    token_lattice,
)
from .field import Field, field_like
from .memory import (
    BatchedConst,
    TargetConst,
    copy_constant_to_target,
    copy_from_target,
    copy_from_target_masked,
    copy_to_target,
    copy_to_target_masked,
    sync_target,
    target_free,
    target_malloc,
    target_malloc_like,
)
from .target import Target, as_target, default_vvl, set_default_vvl
from .spec import FieldSpec, KernelSpec, kernel
from .registry import (
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
from .api import (
    LaunchPlan,
    WindowShapeError,
    WindowVmemError,
    gather_neighbors,
    halo_extend,
    launch_plan,
    pad_sites,
)
from .api import launch as tdp_launch
from .layout import (
    LAYOUTS,
    aosoa_nblocks,
    aosoa_to_soa,
    soa_to_aosoa,
)
from .program import (
    CompiledProgram,
    Program,
    ProgramPlan,
    exchange_ghosts,
    exchange_stats,
    Stage,
    program,
    stage,
)
from .state import FIELD_ATOL, ProgramState, check_fields, validate_field
from .fleet import FleetDriver, FleetProgram, Ticket
from .autotune import (
    Candidate,
    TuneReport,
    TuneResult,
    autotune,
    default_space,
    plane_block_candidates,
    wall_clock_timer,
)
from .costmodel import (
    CostEstimate,
    MachineProfile,
    machine_profile,
    predict,
    roofline_seconds,
)
from .execute import (
    launch,
    launch_stencil,
    reduce,
    site_kernel,
)

__all__ = [
    "Lattice", "token_lattice", "Field", "field_like",
    "Stencil", "STENCIL_D3Q19_PULL", "STENCIL_GRAD_6PT", "STENCIL_GRAD_19PT",
    "D3Q19_VELOCITIES", "launch_stencil",
    "TargetConst", "copy_constant_to_target",
    "copy_to_target", "copy_from_target",
    "copy_to_target_masked", "copy_from_target_masked",
    "sync_target", "target_free", "target_malloc", "target_malloc_like",
    "site_kernel", "launch", "reduce", "default_vvl", "set_default_vvl",
    # declarative API
    "Target", "as_target", "FieldSpec", "KernelSpec", "kernel",
    "tdp_launch", "launch_plan", "LaunchPlan", "gather_neighbors",
    "halo_extend", "pad_sites", "WindowShapeError", "WindowVmemError",
    # memory layout axis (SoA ↔ AoSoA)
    "LAYOUTS", "aosoa_nblocks", "aosoa_to_soa", "soa_to_aosoa",
    "register_executor", "unregister_executor", "get_executor",
    "get_executor_entry", "executor_wants", "executor_tunables",
    "compatible_executors", "list_executors", "registry_version",
    # step graphs
    "Program", "CompiledProgram", "ProgramPlan", "Stage", "program",
    "exchange_ghosts", "exchange_stats",
    "stage",
    # fleets (ensemble execution + async service)
    "BatchedConst", "ProgramState", "validate_field",
    "check_fields", "FIELD_ATOL",
    "FleetProgram", "FleetDriver", "Ticket",
    # autotuning
    "autotune", "default_space", "plane_block_candidates",
    "Candidate", "TuneReport", "TuneResult", "wall_clock_timer",
    # cost model
    "CostEstimate", "MachineProfile", "machine_profile", "predict",
    "roofline_seconds",
]
