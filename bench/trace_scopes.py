"""Name a JAX profiler trace's device time by the program's own stages.

Two tables from one ``.xplane.pb``, over the same traced window and the
same busy time as ``trace_reduce`` (leaf XLA operations, control flow left
out, clipped to ``bench.window``, averaged over the device planes):

- ``device_by_scope``: device time of the leaf operations, grouped by the
  first ``frontier.*`` / ``multisource_csr.*`` component of their ``tf_op``
  path (the engines' ``jax.named_scope`` stages); the rest is ``other``;
- ``idle_by_span``: every idle nanosecond of the window put under the
  innermost ``sssp.*`` host annotation covering it (the program's spans,
  ``repro.obs.trace``), by interval intersection; idle outside all of them
  is ``none``.

``tick_idle_by_span`` splits only the idle time inside the harness's
``bench.tick`` annotations the same way, and ``tick_idle_s`` is the idle
time inside the program's ``sssp.tick`` spans, ``ticks`` their number.

Device and host events sit on one clock only as well as the profiler
aligns them, and on a v5e it puts device events 1.5–1.9 ms early: a
program's first op precedes the host's enqueue of it.  So the device
events are shifted first, by the midpoint of the bounds that each
program's enqueue and completion callback put on the offset
(:func:`clock_offset`; ``clock_offset_ms`` reports it per device).  Busy
and idle totals barely move; what moves is which span a gap falls in.

``jax.profiler.ProfileData`` does not expose an event's metadata, where the
``tf_op`` stat lives, so this module reads the XSpace protobuf itself with
a small wire-format decoder (no protobuf package needed).

    python3 bench/trace_scopes.py <trace.xplane.pb> [<spans.jsonl>]
"""
from __future__ import annotations

import json
import re
import sys

if __package__ in (None, ""):
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench.trace_reduce import CONTROL, WINDOW, _union, op_parts  # noqa: E402

SPAN = "sssp."
TICK = "bench.tick"
ENQUEUE, DONE = "DoEnqueueProgram", "CompleteCallbacks"
STATS = 2
SCOPE = re.compile(r"^(frontier|multisource_csr)\.\w+$")


# -- the XSpace wire format ----------------------------------------------------

def _varint(buf: bytes, i: int) -> tuple:
    b = buf[i]
    if b < 0x80:
        return b, i + 1
    out, shift, i = b & 0x7F, 7, i + 1
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """``(field number, value)`` of one message; a length-delimited value
    is its ``(start, end)`` in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _metadata(buf: bytes, entry: tuple) -> tuple:
    """A map entry of ``XPlane.event_metadata`` / ``stat_metadata``:
    ``(id, name, stats)``, stats as ``[(stat id, str or ref id)]``."""
    mid, name, stats = 0, "", []
    for f, v in _fields(buf, *entry):
        if f == 2:                                  # the value message
            for g, w in _fields(buf, *v):
                if g == 1:
                    mid = _int64(w)
                elif g == 2:
                    name = _str(buf, w)
                elif g == 5:                        # XEventMetadata.stats
                    sid, val = 0, None
                    for h, x in _fields(buf, *w):
                        if h == 1:
                            sid = x
                        elif h == 5:
                            val = _str(buf, x)
                        elif h == 7:                # ref to a stat name
                            val = x
                    stats.append((sid, val))
    return mid, name, stats


def _stat(buf: bytes, stat: tuple, names: dict) -> tuple:
    """``(name, value)`` of one ``XStat`` with an integer or string
    value (int64 read as signed)."""
    sid, val = 0, None
    for f, v in _fields(buf, *stat):
        if f == 1:
            sid = v
        elif f == 3:
            val = v
        elif f == 4:
            val = _int64(v)
        elif f == 5:
            val = _str(buf, v)
    return names.get(sid, ""), val


def _events(buf: bytes, line: tuple, keep, names: dict,
            stat_names: dict) -> tuple:
    """``(line name, [(start_ns, end_ns, metadata id, stats)])`` of the
    events of one ``XLine`` that ``keep(line name, event name)`` keeps;
    ``stats`` is filled only where it returns ``STATS``."""
    name, ts_ns, raw = "", 0, []
    for f, v in _fields(buf, *line):
        if f == 2:
            name = _str(buf, v)
        elif f == 3:
            ts_ns = v
        elif f == 4:
            raw.append(v)
    out = []
    for ev in raw:
        mid = off = dur = 0
        stats = []
        for f, v in _fields(buf, *ev):
            if f == 1:
                mid = _int64(v)
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
            elif f == 4:
                stats.append(v)
        how = keep(name, names.get(mid, ""))
        if how:
            s = ts_ns + off / 1e3
            st = (dict(_stat(buf, x, stat_names) for x in stats)
                  if how == STATS else {})
            out.append((s, s + dur / 1e3, mid, st))
    return name, out


def read_planes(path: str, lines=None) -> list:
    """Every plane of the XSpace at ``path``: ``{"name", "events": {line:
    [(start_ns, end_ns, metadata id, stats)]}, "names": {id: name},
    "tf_op": {id: op path}}``.  ``lines(plane name)`` returns a filter
    ``(line name, event name)`` that keeps an event (true), with its stats
    (``STATS``), or None to skip the plane's events."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, line_spans, events, stat_names = "", [], {}, {}
        meta = []
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _str(buf, v)
            elif g == 3:
                line_spans.append(v)
            elif g == 4:
                meta.append(_metadata(buf, v))
            elif g == 5:
                sid, sname, _ = _metadata(buf, v)
                stat_names[sid] = sname
        names = {mid: n for mid, n, _ in meta}
        tf_op = {}
        for mid, _, stats in meta:
            for sid, val in stats:
                if stat_names.get(sid) == "tf_op":
                    tf_op[mid] = (stat_names.get(val, "")
                                  if isinstance(val, int) else val)
        want = lines(name) if lines is not None else None
        if want is not None:
            for ls in line_spans:
                lname, kept = _events(buf, ls, want, names, stat_names)
                if kept:
                    events.setdefault(lname, []).extend(kept)
        planes.append({"name": name, "events": events, "names": names,
                       "tf_op": tf_op})
    return planes


# -- intervals -----------------------------------------------------------------

def _intersect(a: list, b: list) -> list:
    """Pieces common to two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _innermost(anns: list) -> list:
    """Disjoint sorted ``(start, end, name)`` pieces of the time the
    annotations cover, each named by the innermost annotation over it
    (the latest start; of equal starts, the earliest end)."""
    points = sorted({t for _, s, e in anns for t in (s, e)})
    by_start = sorted(anns, key=lambda a: a[1])
    active, k, out = [], 0, []
    for lo, hi in zip(points, points[1:]):
        while k < len(by_start) and by_start[k][1] <= lo:
            active.append(by_start[k])
            k += 1
        active = [a for a in active if a[2] > lo]
        if active:
            inner = max(active, key=lambda a: (a[1], -a[2]))
            out.append((lo, hi, inner[0]))
    return out


def _attribute(pieces: list, named: list, into: dict, scale: float) -> None:
    """Add each piece's length (times ``scale``) to the name of the
    ``named`` piece over it, ``none`` where nothing is."""
    j = 0
    for s, e in pieces:
        while j < len(named) and named[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(named) and named[k][0] < e:
            lo, hi = max(s, named[k][0]), min(e, named[k][1])
            if hi > lo:
                into[named[k][2]] = into.get(named[k][2], 0.0) + (
                    hi - lo) * scale
                covered += hi - lo
            k += 1
        if e - s > covered:
            into["none"] = into.get("none", 0.0) + (e - s - covered) * scale


def scope_of(tf_op: str) -> str:
    """The first ``frontier.*`` / ``multisource_csr.*`` component of a
    ``tf_op`` (``op path:op type``), else ``other``."""
    for part in tf_op.rsplit(":", 1)[0].split("/"):
        if SCOPE.match(part):
            return part
    return "other"


# -- the reduction ---------------------------------------------------------------

def _lines(plane: str):
    if plane.startswith("/host:"):
        return lambda line, ev: (STATS if ev in (ENQUEUE, DONE) else
                                 ev in (WINDOW, TICK) or ev.startswith(SPAN))
    if plane.startswith("/device:TPU:"):
        return lambda line, ev: {"XLA Ops": True, "XLA Modules": STATS}.get(
            line)
    return None


def clock_offset(plane: dict, host: list) -> tuple:
    """``(shift, low, high)`` in ns: the shift that puts a device plane's
    events on the host's clock, which the profiler does not do exactly.
    No program starts on the device before the host starts to enqueue it
    (``DoEnqueueProgram``, linked by its flow id), and none ends after the
    host starts its completion callbacks (``CompleteCallbacks``): so the
    shift lies in ``[low, high]``, and is taken as their midpoint; 0 where
    the trace has no such pair."""
    enq = {st["_p"]: s for n, s, _, st in host if n == ENQUEUE and "_p" in st}
    done = {st["_c"]: s for n, s, _, st in host if n == DONE and "_c" in st}
    mods = [(s, e, st["_c"]) for s, e, _, st in
            plane["events"].get("XLA Modules", []) if "_c" in st]
    low = max((enq[c] - s for s, _, c in mods if c in enq), default=None)
    high = min((done[c] - e for _, e, c in mods if c in done), default=None)
    if low is None or high is None:
        return 0.0, low, high
    return (low + high) / 2, low, high


def reduce(path: str) -> dict:
    planes = read_planes(path, _lines)
    runtime = [(p["names"][m], s, e, st) for p in planes
               if p["name"].startswith("/host:")
               for evs in p["events"].values() for s, e, m, st in evs]
    host = [(n, s, e) for n, s, e, _ in runtime if n not in (ENQUEUE, DONE)]
    windows = [h for h in host if h[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} annotations in {path}")
    _, w0, w1 = windows[0]
    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    spans = [h for h in host if h[0].startswith(SPAN)]
    named = _innermost(spans)
    ticks = [(max(s, w0), min(e, w1)) for n, s, e in spans
             if n == SPAN + "tick" and min(e, w1) > max(s, w0)]
    bench_ticks = _union([(max(s, w0), min(e, w1)) for n, s, e in host
                          if n == TICK and min(e, w1) > max(s, w0)])

    scale = 1e-9 / len(devices)
    by_scope, by_span, tick_split = {}, {}, {}
    tick_idle, offsets = 0.0, []
    for plane in devices:
        shift, low, high = clock_offset(plane, runtime)
        offsets.append([x / 1e6 if x is not None else None
                        for x in (shift, low, high)])
        busy = []
        for s, e, mid, _ in plane["events"].get("XLA Ops", []):
            s, e = max(s + shift, w0), min(e + shift, w1)
            if e <= s or op_parts(plane["names"].get(mid, ""))[1] in CONTROL:
                continue
            busy.append((s, e))
            key = scope_of(plane["tf_op"].get(mid, ""))
            by_scope[key] = by_scope.get(key, 0.0) + (e - s) * scale
        merged = _union(busy)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        _attribute(gaps, named, by_span, scale)
        _attribute(_intersect(gaps, bench_ticks), named, tick_split, scale)
        tick_idle += sum(e - s for s, e in _intersect(gaps, _union(ticks))
                         ) * scale

    def ranked(table):
        return sorted(([k, v] for k, v in table.items()),
                      key=lambda kv: -kv[1])

    return {"device_by_scope": ranked(by_scope),
            "idle_by_span": ranked(by_span),
            "tick_idle_by_span": ranked(tick_split),
            "tick_idle_s": tick_idle, "ticks": len(ticks),
            "clock_offset_ms": offsets}


def readings(scopes: dict, spans: list) -> dict:
    """The stage readings of a traced window, from :func:`reduce`'s dict
    and the program's spans of the same window: ``tick_idle_ms``, idle
    time inside ``sssp.tick`` per tick; ``compact_ms.p2p``, device time of
    ``frontier.compact`` per sweep of the p2p solves; and
    ``relax_ns_per_edge.p2p``, device time of ``frontier.relax`` per edge
    those solves relaxed."""
    by_scope = dict(scopes["device_by_scope"])
    out = {}
    if scopes["ticks"]:
        out["tick_idle_ms"] = scopes["tick_idle_s"] / scopes["ticks"] * 1e3
    p2p = [s for s in spans if s.name == "p2p_solve"]
    sweeps = sum(s.args.get("sweeps", 0) for s in p2p)
    edges = sum(s.args.get("edges_relaxed", 0) for s in p2p)
    if sweeps and "frontier.compact" in by_scope:
        out["compact_ms.p2p"] = by_scope["frontier.compact"] / sweeps * 1e3
    if edges and "frontier.relax" in by_scope:
        out["relax_ns_per_edge.p2p"] = by_scope["frontier.relax"] / edges * 1e9
    return out


def main(argv: list) -> int:
    """``trace_scopes.py <trace.xplane.pb> [<spans.jsonl>]``: the tables,
    and with the spans (``Tracer.write_jsonl``) the readings, as JSON."""
    import types

    out = reduce(argv[0])
    if len(argv) > 1:
        with open(argv[1]) as f:
            rows = [json.loads(line) for line in f]
        spans = [types.SimpleNamespace(name=r["name"], args=r["args"])
                 for r in rows if r["kind"] == "span"]
        out["readings"] = readings(out, spans)
    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
