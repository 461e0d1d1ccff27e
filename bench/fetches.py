"""The scheduler's ``fetch`` spans (each device→host read of a solve's
results, with its ``bytes``) per solve, shared by the ``fetch_*`` metric
readers in ``bench/metrics/``."""
from __future__ import annotations

from bench.spans import SOLVE


def per_solve(spans, kind: str, value) -> float | None:
    """``value(span)`` summed over the ``fetch`` spans of the ticks whose
    solves are all of ``kind``, over the number of those solves; None
    where no tick holds a ``fetch`` span."""
    name = SOLVE[kind]
    total, solves, seen = 0.0, 0, False
    for t in (s for s in spans if s.name == "tick"):
        inside = [s for s in spans
                  if s is not t and t.t0 <= s.t0 and s.t1 <= t.t1]
        kinds = {s.name for s in inside if s.name in SOLVE.values()}
        if kinds != {name}:
            continue
        fetched = [s for s in inside if s.name == "fetch"]
        seen = seen or bool(fetched)
        total += sum(value(s) for s in fetched)
        solves += sum(s.name == name for s in inside)
    return total / solves if seen and solves else None
