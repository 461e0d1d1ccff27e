"""Mean time of the device→host reads of a traced p2p solve: the
scheduler's ``fetch`` spans in its tick (the engine's scalars, and the
row read after the solve), ms."""
from bench import fetches


def read(ctx):
    v = fetches.per_solve(ctx["spans"], "p2p", lambda s: s.duration)
    return v * 1e3 if v is not None else None
