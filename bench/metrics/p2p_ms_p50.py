"""Median latency of every p2p answer in the window, in ms."""
from bench import spans, stats


def read(ctx):
    lat = [l for l, _ in spans.queries(ctx["window"], "p2p")]
    return stats.percentile(lat, 50) * 1e3 if len(lat) >= 2 else None
