"""Mean host self time of the traced ticks that ran a batch solve: the
scheduler's ``tick`` span less its solve children (program spans), ms."""
from bench import spans


def read(ctx):
    t = spans.tick_self_s(ctx["spans"], "rows")
    return sum(t) / len(t) * 1e3 if t else None
