"""The paper's primary contribution: the SPD stream-computing DSL, its
compiler to JAX, (n, m) parallelism transforms, and the design-space
exploration engine."""

from .codegen import CodegenError, StencilSummary, StreamKernel, stencil_summary
from .compiler import CompiledCore, HardwareReport, Registry, SPDCompileError
from .dfg import Core, Node, SPDError, SPDGraphError, schedule
from .distribute import ShardedStreamKernel, device_axis_values
from .dse import DesignPoint, FPGAModel, StreamWorkload, TPUModel
from .explorer import Explorer, Sweep, pareto_mask
from .legalize import (
    VMEM_BYTES,
    blocking_plan,
    legal_block_values,
    resolve_run_plan,
    shard_height,
)
from .library import LibraryModule, default_registry_modules
from .measure import (
    BackendCalibration,
    MeasurementCache,
    calibrate_backend,
    calibrate_execution,
    core_fingerprint,
    time_run,
)
from .search import (
    ExecutedPoint,
    ExhaustiveSearch,
    LocalRefine,
    SearchResult,
    SearchRunner,
    SearchStrategy,
    SuccessiveHalving,
    get_strategy,
)
from .spd import SPDParseError, parse_spd, parse_spd_file
from .transforms import (
    spatial_duplicate,
    spatial_duplicate_spd,
    temporal_cascade,
    temporal_cascade_spd,
)

__all__ = [
    "BackendCalibration",
    "CodegenError",
    "CompiledCore",
    "Core",
    "DesignPoint",
    "ExecutedPoint",
    "ExhaustiveSearch",
    "Explorer",
    "FPGAModel",
    "LocalRefine",
    "HardwareReport",
    "LibraryModule",
    "MeasurementCache",
    "Node",
    "Registry",
    "SPDCompileError",
    "SPDError",
    "SPDGraphError",
    "SPDParseError",
    "SearchResult",
    "SearchRunner",
    "SearchStrategy",
    "ShardedStreamKernel",
    "StencilSummary",
    "StreamKernel",
    "StreamWorkload",
    "SuccessiveHalving",
    "Sweep",
    "TPUModel",
    "VMEM_BYTES",
    "blocking_plan",
    "calibrate_backend",
    "calibrate_execution",
    "core_fingerprint",
    "default_registry_modules",
    "device_axis_values",
    "get_strategy",
    "legal_block_values",
    "pareto_mask",
    "parse_spd",
    "parse_spd_file",
    "resolve_run_plan",
    "schedule",
    "shard_height",
    "spatial_duplicate",
    "spatial_duplicate_spd",
    "stencil_summary",
    "temporal_cascade",
    "temporal_cascade_spd",
    "time_run",
]
