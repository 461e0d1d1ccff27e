"""Observability layer: unified metrics, solve-level tracing, cost records.

- `repro.obs.metrics` — process-local counter/gauge registry
  with labeled series and a deterministic `snapshot()` contract; the
  serving components' `stats()` dicts are views over it.
- `repro.obs.trace` — hierarchical spans (tick → batch_solve/p2p_solve
  → stage/launch/wait/fetch; repair, mutate) with Chrome-trace + JSONL
  export, ``sssp.*`` annotations on the JAX profiler's trace, an
  injected clock, and a no-op singleton when disabled.
- `repro.obs.profile` — per-solve cost records
  ``(engine, statics, shape) → wall_ms, sweeps, edges``, the training
  data for ROADMAP item 4's measured cost model.
- `repro.obs.validate` — schema + answer-chain validation for the
  exported artifacts (also a CLI for CI).
- `repro.obs.capture` — install/finalize helpers shared by the launch
  drivers' ``--trace-out`` paths.
"""
from .capture import cost_path_for, finalize_capture, install_capture
from .metrics import (
    Counter,
    Gauge,
    MetricsRegistry,
    default_registry,
    mark_trace,
)
from .profile import (CostLog, CostRecord, NULL_COST_LOG, backend_info,
                      get_cost_log, set_cost_log)
from .trace import NULL_TRACER, NullTracer, Span, Tracer, get_tracer, set_tracer

__all__ = [
    "cost_path_for",
    "finalize_capture",
    "install_capture",
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "default_registry",
    "mark_trace",
    "backend_info",
    "CostLog",
    "CostRecord",
    "NULL_COST_LOG",
    "get_cost_log",
    "set_cost_log",
    "NULL_TRACER",
    "NullTracer",
    "Span",
    "Tracer",
    "get_tracer",
    "set_tracer",
]
