"""Mean time of the device→host reads of a traced batch solve: the
scheduler's ``fetch`` spans in its tick (the rows and the engine's
scalars), ms."""
from bench import fetches


def read(ctx):
    v = fetches.per_solve(ctx["spans"], "rows", lambda s: s.duration)
    return v * 1e3 if v is not None else None
