"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers: the busy time (union of the intervals in which an XLA operation
ran; a control-flow operation such as ``while``, whose event spans the
operations of its body, is left out) and the idle time of the traced window, device time per operation and
per XLA module, and the idle gaps, each named by the innermost ``bench.*``
host annotation that covers it.

The window is the host annotation ``bench.window`` (the harness opens it
when the profiler starts and closes it before the profiler stops); device
intervals are clipped to it.  Numbers are averaged over the device planes.
"""
from __future__ import annotations

import bisect
import re

WINDOW = "bench.window"
PREFIX = "bench."
_HASH = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
CONTROL = ("while", "conditional", "call")


def _union(intervals: list) -> list:
    """Merged ``(start, end)`` intervals, sorted."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def op_parts(hlo: str) -> tuple:
    """``'%fusion.5 = f32[8,4]{1,0} fusion(...), kind=kCustom'`` ->
    ``('fusion.5 f32[8,4] fusion', 'fusion')``: a label with the output
    shape (layout dropped), and the opcode."""
    name, _, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    m = _OPCODE.search(" " + rest)
    if not m:
        return name, ""
    shape = _LAYOUT.sub("", rest[:m.start()]).strip()[:60]
    return f"{name} {shape} {m.group(1)}", m.group(1)


def _clip(s: float, e: float, w0: float, w1: float):
    return max(s, w0), min(e, w1)


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    windows = [h for h in host if h[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW} annotations in {path}")
    _, w0, w1 = windows[0]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")

    busy_ns, ops, modules, gaps = 0.0, {}, {}, []
    for plane in devices:
        spans = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            table = ops if line.name == "XLA Ops" else modules
            for ev in line.events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             w0, w1)
                if e <= s:
                    continue
                if line.name == "XLA Ops":
                    key, opcode = op_parts(ev.name)
                    if opcode in CONTROL:
                        continue
                    spans.append((s, e))
                else:
                    key = _HASH.sub("", ev.name)
                table[key] = table.get(key, 0.0) + (e - s)
        merged = _union(spans)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    ndev = len(devices)
    inner = sorted((s, e, n) for n, s, e in host if n != WINDOW)
    starts = [s for s, _, _ in inner]
    named = [(_host_name(inner, starts, (s + e) / 2), (e - s) / 1e9)
             for s, e in gaps]
    named.sort(key=lambda g: -g[1])
    by_host: dict = {}
    for name, sec in named:
        by_host[name] = by_host.get(name, 0.0) + sec / ndev

    def ranked(table):
        return sorted(([k, v / 1e9 / ndev] for k, v in table.items()),
                      key=lambda kv: -kv[1])

    return {"busy_s": busy_ns / 1e9 / ndev, "window_s": (w1 - w0) / 1e9,
            "devices": ndev, "device_ops": ranked(ops),
            "modules": ranked(modules),
            "idle_gaps": [[n, s] for n, s in named],
            "idle_by_host": sorted(([k, v] for k, v in by_host.items()),
                                   key=lambda kv: -kv[1])}


def _host_name(inner: list, starts: list, t: float) -> str:
    """The latest-starting ``bench.*`` annotation (the window's excepted;
    the harness's own are never nested) that covers ``t``, else the
    window's name."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and inner[i][1] >= t:
        return inner[i][2]
    return WINDOW
