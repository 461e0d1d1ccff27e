"""Engine-selection seam: one place that decides single-device vs
vertex-partitioned sharded solves.

Both entry points into the engine stack route through here instead of
hard-coding engine names: ``core.api.shortest_paths(engine="auto")`` for
one-shot callers, and ``MicroBatchScheduler`` for every served batch /
point-to-point solve (serve/scheduler.py takes a ``dispatch=`` policy).
Centralizing the choice keeps the two paths answering identically and
gives operators a single knob set.

The policy mirrors the paper's own crossover: the MPI arm wins only once
the per-rank block is big enough to hide the exchange (its Table III
speedups start at the largest graphs), so small graphs stay on the
single-device engines and only graphs with ``n >= shard_threshold``
route to the partitioned ones — and only when the runtime actually has
multiple devices to partition across.  Below the shard crossover, large
single-source solves on static CSR graphs route to the Δ-stepping
engine (core/delta_stepping.py) when the graph's weight profile keeps
its light in-ELL narrow — ``delta_threshold`` / ``would_delta`` gate
this, and the answers stay bitwise-identical either way.  Dynamic graphs (PR 5 overlays)
never shard: their serving path relies on overlay-native operands and
incremental repair, both of which are built on the single-device staged
views (a frozen CsrPartition would go stale at the first mutation).

The mesh is built once per (nprocs, axis) and cached module-wide —
serving solves hundreds of queries per second and mesh construction is
not free.  ``EngineChoice.nprocs`` doubles as the DistanceCache shard
arity: row keys of sharded-served rows carry the source's owner shard
(``registry.GraphHandle.row_key(..., shards=nprocs)``), the
cache-locality layout of "Optimizing Dijkstra for real-world
performance" (arXiv 1505.05033) — rows live with their owner.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Optional

import jax
import numpy as np

# crossover below which partitioning loses to a single device on the
# emulated host mesh (benchmarks/serve_bench.py gates the >= side at 4
# devices); operators override per deployment via DispatchPolicy.
DEFAULT_SHARD_THRESHOLD = 20000

# vertex count from which single-device single-source solves try the
# Δ-stepping engine: below it the frontier engine's per-sweep compaction
# is cheap enough that the Δ split/staging isn't worth it (the
# benchmarks/run_bench.py gate_delta corpora sit well above).  Routing
# additionally requires the graph's delta_profile to be routable (narrow
# light in-ELL) — see DispatchPolicy.would_delta.
DEFAULT_DELTA_THRESHOLD = 4096

# query kinds the scheduler distinguishes (scheduler.tick's two solve
# paths plus api's one-shot single-source case).
KINDS = ("single", "batch", "p2p")


@functools.lru_cache(maxsize=None)
def serving_mesh(nprocs: int, axis: str = "data") -> jax.sharding.Mesh:
    """The serving layer's cached 1-D mesh over the first ``nprocs``
    devices (forced host devices in CI and tests, chips on a TPU host)."""
    from repro.core._axes import make_mesh

    return make_mesh((nprocs,), (axis,), devices=jax.devices()[:nprocs])


@dataclasses.dataclass(frozen=True)
class EngineChoice:
    """One routing decision: which engine, on which mesh (None for the
    single-device engines), and the shard arity cache keys must carry.

    The optional statics fields let a policy return not just the engine
    but its tuning parameters, so every caller's magic numbers route
    through this one seam (ROADMAP item 4): ``delta`` is the Δ-bucket
    width for the engines that consume one, ``chunk`` the frontier
    engines' scatter chunk, ``batch_cap`` the padded multisource bucket
    ceiling the scheduler should admit per tick.  ``None`` (the
    threshold policy's value) means "caller keeps its default" — the
    measured-model policy (repro/tune/select.py) fills them from
    calibrated data.  ``via`` names which arm decided: ``"threshold"``
    for the hard-coded size rules, ``"model"`` for a fitted cost model.
    """
    engine: str
    mesh: Optional[jax.sharding.Mesh]
    axis: str = "data"
    nprocs: int = 1
    delta: Optional[float] = None
    chunk: Optional[int] = None
    batch_cap: Optional[int] = None
    via: str = "threshold"

    @property
    def sharded(self) -> bool:
        return self.nprocs > 1


class DispatchPolicy:
    """Size-threshold routing between the single-device and sharded CSR
    engine families.

    shard_threshold: vertex count at which graphs route sharded
        (inclusive).  ``None`` disables sharding outright.
    nprocs: devices to partition across; default = every visible device.
        More than are visible raises ``ValueError``; 1 disables sharding.
    axis: mesh axis name (matches the sharded engines' default).
    delta_threshold: vertex count at which non-sharded single-source
        solves on static CsrGraphs route to the Δ-stepping engine
        (inclusive), when the graph's weight profile supports it.
        ``None`` disables Δ routing.
    """

    def __init__(self, *, shard_threshold: int | None = DEFAULT_SHARD_THRESHOLD,
                 nprocs: int | None = None, axis: str = "data",
                 delta_threshold: int | None = DEFAULT_DELTA_THRESHOLD):
        avail = len(jax.devices())
        self.nprocs = avail if nprocs is None else int(nprocs)
        if not 1 <= self.nprocs <= avail:
            raise ValueError(
                f"nprocs={self.nprocs} needs 1..{avail} devices; "
                f"{avail} are visible ({jax.devices()[0].platform})")
        self.shard_threshold = shard_threshold
        self.delta_threshold = delta_threshold
        self.axis = axis

    # engine per (family, kind); p2p stays on frontier single-device for
    # the target= early exit — sharded p2p runs the full fixpoint instead
    # (superset row, same dist[target] bytes) which the scheduler then
    # caches as a COMPLETE row, unlike the partial target= rows.
    _SINGLE = {"single": "frontier", "batch": "multisource_csr",
               "p2p": "frontier"}
    _SHARDED = {"single": "frontier_sharded",
                "batch": "multisource_csr_sharded",
                "p2p": "frontier_sharded"}

    def would_shard(self, n: int, *, dynamic: bool = False) -> bool:
        """Pure size check — no mesh/staging side effects, so callers
        (scheduler, registry) can compute deterministic cache-key shapes
        before anything is staged."""
        return (not dynamic
                and self.shard_threshold is not None
                and self.nprocs > 1
                and n >= self.shard_threshold)

    def would_delta(self, g, n: int, *, dynamic: bool = False) -> bool:
        """Whether a non-sharded single-source solve of ``g`` should use
        the Δ-stepping engine: a static (non-dynamic) CsrGraph at or
        above ``delta_threshold`` whose weight distribution yields a
        narrow light in-ELL (``delta_profile(g)["routable"]`` — dense or
        hub-in-degree-skewed graphs stay on the frontier engine, whose
        compacted push doesn't pay the pull's O(n·K_light) pass).  The
        profile is memoized on the graph, so repeat routing of a pinned
        handle is a dict lookup.  Only graphs that actually carry CSR
        arrays qualify — dense arrays / Graph inputs keep the frontier
        engine rather than paying a host-side conversion just to route.
        """
        if (dynamic or self.delta_threshold is None
                or n < self.delta_threshold):
            return False
        if getattr(g, "indptr", None) is None:      # not CSR-backed
            return False
        from repro.core.delta_stepping import delta_profile

        return bool(delta_profile(g)["routable"])

    def batch_cap(self, g) -> Optional[int]:
        """Per-tick distinct-source admission ceiling for batched solves
        of ``g``, or ``None`` for "scheduler keeps its ``max_batch``".
        Pure (no mesh/staging), called at admission time — the threshold
        policy has no opinion; the measured-model policy returns the
        calibrated bucket size (tune/select.py)."""
        return None

    def choose(self, g, *, kind: str = "single") -> EngineChoice:
        """Route one solve.  ``g`` is anything with an ``n`` (CsrGraph,
        Graph, DynamicGraph, GraphHandle-like) or a dense square array;
        dynamic graphs are detected and pinned to the single-device
        family (see module docstring)."""
        if kind not in KINDS:
            raise ValueError(f"unknown kind {kind!r}; choose from {KINDS}")
        from repro.dynamic.overlay import DynamicGraph  # local: serve<->dyn

        dynamic = isinstance(g, DynamicGraph) or getattr(g, "dyn", None) is not None
        n = getattr(g, "n", None)
        if n is None:
            n = int(np.asarray(g).shape[0])
        if self.would_shard(int(n), dynamic=dynamic):
            return EngineChoice(self._SHARDED[kind],
                                serving_mesh(self.nprocs, self.axis),
                                self.axis, self.nprocs)
        # kind="single" only (batch wants the shared-gather multisource
        # engine, p2p the target= early exit the Δ engine doesn't have).
        if kind == "single" and self.would_delta(g, int(n), dynamic=dynamic):
            return EngineChoice("delta_stepping", None, self.axis, 1)
        return EngineChoice(self._SINGLE[kind], None, self.axis, 1)


_DEFAULT: Optional[DispatchPolicy] = None


def default_policy() -> DispatchPolicy:
    """Process-wide policy used by ``shortest_paths(engine="auto")`` and
    by schedulers constructed without an explicit ``dispatch=``."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = DispatchPolicy()
    return _DEFAULT


def set_default_policy(
        policy: Optional[DispatchPolicy]) -> Optional[DispatchPolicy]:
    """Install (or with ``None`` reset) the process-wide policy — the
    launcher wires its ``--shard-threshold`` / ``--devices`` flags here.
    Returns the PREVIOUS policy (``None`` if it was still the lazy
    default) so callers can restore it; prefer :func:`policy_override`
    for scoped swaps."""
    global _DEFAULT
    prev = _DEFAULT
    _DEFAULT = policy
    return prev


@contextlib.contextmanager
def policy_override(policy: Optional[DispatchPolicy]):
    """Scoped :func:`set_default_policy`: installs ``policy`` for the
    ``with`` body and restores the previous one on exit (exception
    included) — how tests and the tuner race two policies without
    leaking global state.  Yields the installed policy."""
    prev = set_default_policy(policy)
    try:
        yield policy
    finally:
        set_default_policy(prev)
