"""Process-local metrics registry: counters and gauges.

One uniform namespace for every counter the serving stack keeps —
`DistanceCache`, `GraphRegistry`, and `MicroBatchScheduler` all hang
their counters off a `MetricsRegistry` and derive their legacy
``stats()`` dicts from `snapshot()`.

Design constraints (see ISSUE 9):

- **Process-local, not global-only.**  Each component owns (or is
  handed) a registry instance, so two schedulers in one process never
  alias each other's counters.  A module-level `default_registry()`
  exists for process-wide series — the jit-retrace counter lives
  there, because jitted engine functions are module-level objects.
- **Deterministic snapshots.**  `snapshot()` returns a flat
  ``{qualified_name: number}`` dict in sorted-key order containing
  only event counts and set gauges — no wall-clock values — so two
  same-seed replays produce byte-identical snapshots (the chaos
  determinism test relies on this).
- **Cheap increments.**  `Counter.inc` is one int add; the serving hot
  path calls it unconditionally, so it must stay trivial.

Series are keyed on ``(name, sorted(labels))``; the qualified name
renders as ``name{k=v,...}``.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterator, Optional, Tuple

__all__ = [
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "default_registry",
    "mark_trace",
]


def _qualify(name: str, labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in labels)
    return f"{name}{{{inner}}}"


class Counter:
    """Monotonically increasing event count."""

    __slots__ = ("name", "labels", "_value")

    def __init__(self, name: str, labels: Tuple[Tuple[str, str], ...] = ()):
        self.name = name
        self.labels = labels
        self._value = 0

    def inc(self, k: int = 1) -> None:
        self._value += k

    @property
    def value(self) -> int:
        return self._value

    def reset(self) -> None:
        self._value = 0


class Gauge:
    """Point-in-time value: either set explicitly or computed at
    snapshot time via a callback (``fn=``)."""

    __slots__ = ("name", "labels", "_value", "_fn")

    def __init__(
        self,
        name: str,
        labels: Tuple[Tuple[str, str], ...] = (),
        fn: Optional[Callable[[], float]] = None,
    ):
        self.name = name
        self.labels = labels
        self._value = 0.0
        self._fn = fn

    def set(self, v: float) -> None:
        self._value = float(v)

    def add(self, v: float) -> None:
        self._value += float(v)

    @property
    def value(self) -> float:
        if self._fn is not None:
            return float(self._fn())
        return self._value


class MetricsRegistry:
    """Get-or-create store of named, labeled series."""

    def __init__(self) -> None:
        self._series: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], object] = {}

    def _key(self, name: str, labels: Dict[str, str]) -> Tuple[str, Tuple[Tuple[str, str], ...]]:
        return (name, tuple(sorted((str(k), str(v)) for k, v in labels.items())))

    def counter(self, name: str, **labels: str) -> Counter:
        key = self._key(name, labels)
        s = self._series.get(key)
        if s is None:
            s = Counter(name, key[1])
            self._series[key] = s
        elif not isinstance(s, Counter):
            raise TypeError(f"series {name!r} already registered as {type(s).__name__}")
        return s

    def gauge(self, name: str, fn: Optional[Callable[[], float]] = None, **labels: str) -> Gauge:
        key = self._key(name, labels)
        s = self._series.get(key)
        if s is None:
            s = Gauge(name, key[1], fn=fn)
            self._series[key] = s
        elif not isinstance(s, Gauge):
            raise TypeError(f"series {name!r} already registered as {type(s).__name__}")
        return s

    def series(self) -> Iterator[object]:
        return iter(self._series.values())

    def find(self, name: str) -> list:
        """Every series registered under ``name`` (any label set)."""
        return [s for (n, _), s in self._series.items() if n == name]

    def snapshot(self) -> Dict[str, float]:
        """Flat, sorted, deterministic view of every series.

        Counters report their count, gauges their current value.
        """
        out: Dict[str, float] = {}
        for (name, labels), s in self._series.items():
            q = _qualify(name, labels)
            if isinstance(s, Counter):
                out[q] = s.value
            elif isinstance(s, Gauge):
                out[q] = s.value
        return dict(sorted(out.items()))

    def reset(self) -> None:
        for s in self._series.values():
            if isinstance(s, Counter):
                s.reset()
            elif isinstance(s, Gauge):
                if s._fn is None:
                    s.set(0.0)


_DEFAULT: MetricsRegistry = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-wide registry.

    Holds series whose natural scope is the process, not a component
    instance — most importantly ``jit.retrace{fn=...}``, because the
    jitted engine functions it instruments are module-level objects.
    """
    return _DEFAULT


def mark_trace(fn_name: str) -> None:
    """Record one jit trace of ``fn_name``.

    Called from *inside* jitted function bodies: the Python body only
    executes while jax is tracing, so each call marks exactly one
    (re)trace and costs nothing on cached executions.
    """
    _DEFAULT.counter("jit.retrace", fn=fn_name).inc()
