"""Per-solve cost records — the training data for a measured cost model.

Every engine solve (batched multisource, p2p early-exit, API-level
single source, dynamic repair) can emit one `CostRecord` mapping the
*decision inputs* an engine selector would see —
``(engine, n, m, batch, nprocs, delta)`` — to the *measured outcome*
``(sweeps, edges_relaxed, wall_ms, converged)``.  ROADMAP item 4's
self-tuning dispatch fits its cost model on exactly these rows.

Emission follows the tracer pattern: a module-level no-op `CostLog`
singleton, replaced by the launch drivers when ``--trace-out`` is
given.  `emit()` on the null log is a constant-time early return, so
instrumented call sites cost nothing in normal runs.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional

__all__ = [
    "CostRecord",
    "CostLog",
    "NULL_COST_LOG",
    "backend_info",
    "get_cost_log",
    "set_cost_log",
]

# schema v1: the decision/outcome fields every record must carry.
COST_RECORD_FIELDS_V1 = (
    "engine",
    "graph",
    "n",
    "m",
    "batch",
    "nprocs",
    "delta",
    "sweeps",
    "edges_relaxed",
    "wall_ms",
    "converged",
)

# schema v2 adds the hardware identity: a cost model fitted on one
# backend is meaningless on another (the paper's MPI/CUDA crossover
# moves with the hardware), so records name where they were measured.
# obs/validate.py accepts both versions; new emitters always write v2.
COST_RECORD_FIELDS_V2_EXTRA = ("backend", "device_kind")
COST_RECORD_FIELDS = COST_RECORD_FIELDS_V1 + COST_RECORD_FIELDS_V2_EXTRA
COST_RECORD_SCHEMA = 2

_backend_info: Optional[tuple] = None


def backend_info() -> tuple:
    """``(backend, device_kind)`` of the running process — e.g.
    ``("cpu", "cpu")`` or ``("gpu", "NVIDIA A100...")``.  Cached after
    the first call; jax is imported lazily so a pure log-reading process
    never initializes a backend."""
    global _backend_info
    if _backend_info is None:
        import jax

        devs = jax.devices()
        _backend_info = (str(jax.default_backend()),
                         str(devs[0].device_kind) if devs else "")
    return _backend_info


@dataclasses.dataclass(frozen=True)
class CostRecord:
    """One solve: decision inputs → measured outcome."""

    engine: str          # which engine ran (bellman_csr, frontier, ...)
    graph: str           # registry graph name, or "" outside serving
    n: int               # vertex count
    m: int               # edge count
    batch: int           # padded multisource bucket size (1 for p2p/single)
    nprocs: int          # mesh size for sharded solves, else 1
    delta: float         # Δ-stepping bucket width, 0.0 when not applicable
    sweeps: int          # relaxation sweeps / bucket phases executed
    edges_relaxed: int   # total edge relaxations performed
    wall_ms: float       # host wall-clock for the solve, ms
    converged: bool      # fixpoint reached within the sweep cap
    backend: str = ""    # jax.default_backend() at measurement (v2)
    device_kind: str = ""  # device_kind of device 0 at measurement (v2)
    scan_steps: int = 0  # doubling steps of each sweep's segmented min-scan

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class CostLog:
    """Append-only in-memory cost-record sink with a JSONL exporter."""

    enabled = True

    def __init__(self) -> None:
        self.records: List[CostRecord] = []

    def emit(
        self,
        *,
        engine: str,
        n: int,
        m: int,
        sweeps: int,
        edges_relaxed: int,
        wall_ms: float,
        converged: bool,
        graph: str = "",
        batch: int = 1,
        nprocs: int = 1,
        delta: float = 0.0,
        backend: str = "",
        device_kind: str = "",
        scan_steps: int = 0,
    ) -> None:
        if not backend or not device_kind:
            # v2: stamp the measuring hardware, so a record says which
            # backend and device kind its wall_ms came from.
            be, dk = backend_info()
            backend = backend or be
            device_kind = device_kind or dk
        self.records.append(
            CostRecord(
                engine=str(engine),
                graph=str(graph),
                n=int(n),
                m=int(m),
                batch=int(batch),
                nprocs=int(nprocs),
                delta=float(delta),
                sweeps=int(sweeps),
                edges_relaxed=int(edges_relaxed),
                wall_ms=float(wall_ms),
                converged=bool(converged),
                backend=str(backend),
                device_kind=str(device_kind),
                scan_steps=int(scan_steps),
            )
        )

    def write_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            for r in self.records:
                f.write(json.dumps(r.to_dict()) + "\n")

    def clear(self) -> None:
        self.records.clear()


class _NullCostLog(CostLog):
    """Disabled sink: emit() drops the record before building it."""

    enabled = False

    def __init__(self) -> None:
        self.records = []

    def emit(self, **kwargs: Any) -> None:  # noqa: D102 - no-op
        return None


NULL_COST_LOG = _NullCostLog()

_current: CostLog = NULL_COST_LOG


def get_cost_log() -> CostLog:
    return _current


def set_cost_log(log: Optional[CostLog]) -> CostLog:
    """Install ``log`` process-wide; returns the previous one."""
    global _current
    prev = _current
    _current = log if log is not None else NULL_COST_LOG
    return prev
