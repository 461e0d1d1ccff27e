"""Mean bytes read from the device for a traced p2p solve: the ``bytes``
of the scheduler's ``fetch`` spans in its tick, B."""
from bench import fetches


def read(ctx):
    return fetches.per_solve(ctx["spans"], "p2p",
                             lambda s: s.args.get("bytes", 0))
