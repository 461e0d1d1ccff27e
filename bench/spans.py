"""Reductions of the window's records and of the program's spans, shared by
the metric readers in ``bench/metrics/``."""
from __future__ import annotations

SOLVE = {"p2p": "p2p_solve", "rows": "batch_solve"}


def queries(window, kind: str) -> list:
    """``(latency_s, done)`` of every query of ``kind`` in the window,
    the latency from the send of its job to the job's last answer."""
    out = []
    for s in window.sent:
        for src, tgt in s.job.queries:
            if (tgt is not None) == (kind == "p2p"):
                out.append((s.done - s.sent, s.done))
    return out


def solves(spans, kind: str) -> list:
    return [s for s in spans if s.name == SOLVE[kind]]


def tick_self_s(spans, kind: str) -> list:
    """Host self time of each tick that ran a solve of ``kind``: the tick
    span less its solve children."""
    name = SOLVE[kind]
    out = []
    for t in (s for s in spans if s.name == "tick"):
        kids = [s for s in spans if s.name in SOLVE.values()
                and t.t0 <= s.t0 and s.t1 <= t.t1]
        if any(s.name == name for s in kids):
            out.append(t.duration - sum(s.duration for s in kids))
    return out
