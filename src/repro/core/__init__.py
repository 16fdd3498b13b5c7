"""repro.core — PATSMA (Parameter Auto-Tuning for Shared Memory Algorithms)
ported to JAX: staged numerical optimizers (CSA, Nelder–Mead), the Autotuning
driver with Single-Iteration / Entire-Execution × Runtime / user-cost modes,
search-space codecs, and the cost backends used across the framework.
"""
from .autotuning import Autotuning
from .costs import (
    TPU_V5E,
    PEAKS,
    peaks_for,
    CachePartition,
    ExecutableCache,
    HardwareSpec,
    RooflineTerms,
    RuntimeCost,
    aot_compile,
    collective_bytes,
    compile_fanout,
    hlo_flops_bytes,
    roofline_terms,
)
from .csa import CSA
from .grid_random import GridSearch, RandomSearch
from .guard import (
    CircuitBreaker,
    FaultPolicy,
    GuardTimeout,
    Quarantine,
    SandboxCrash,
    deterministic_backoff,
    guarded_call,
    is_transient_failure,
    sandboxed_probe,
)
from .measure import (
    MeasureEngine,
    MeasurePolicy,
    MeasureResult,
    NoiseEstimate,
    resolve_measure_policy,
    time_rep,
)
from .nelder_mead import NelderMead
from .optimizer import NumericalOptimizer
from .space import ChoiceDim, Constraint, FloatDim, IntDim, LogIntDim, SearchSpace
from .strategy import (
    Pipeline,
    Portfolio,
    SearchStrategy,
    cull_laggards,
    make_strategy,
    strategy_label,
)
from .tuned_jit import TunedStep

__all__ = [
    "Autotuning",
    "CSA",
    "NelderMead",
    "GridSearch",
    "RandomSearch",
    "NumericalOptimizer",
    "SearchStrategy",
    "Pipeline",
    "Portfolio",
    "cull_laggards",
    "make_strategy",
    "strategy_label",
    "SearchSpace",
    "Constraint",
    "IntDim",
    "FloatDim",
    "LogIntDim",
    "ChoiceDim",
    "TunedStep",
    "RuntimeCost",
    "MeasurePolicy",
    "MeasureResult",
    "MeasureEngine",
    "NoiseEstimate",
    "resolve_measure_policy",
    "time_rep",
    "ExecutableCache",
    "CachePartition",
    "aot_compile",
    "compile_fanout",
    "FaultPolicy",
    "GuardTimeout",
    "SandboxCrash",
    "CircuitBreaker",
    "Quarantine",
    "guarded_call",
    "sandboxed_probe",
    "is_transient_failure",
    "deterministic_backoff",
    "HardwareSpec",
    "RooflineTerms",
    "TPU_V5E",
    "PEAKS",
    "peaks_for",
    "collective_bytes",
    "hlo_flops_bytes",
    "roofline_terms",
]
