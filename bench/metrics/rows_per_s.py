"""Full rows answered over the time from the window's start to the last
answer."""
from bench import spans, stats


def read(ctx):
    q = spans.queries(ctx["window"], "rows")
    if not q:
        return None
    return stats.rate(len(q), ctx["window"].start, max(d for _, d in q))
