"""Share of the traced interval of p2p jobs in which no operation ran on
the device, from the profiler trace, %."""
from bench import spans


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["busy_s"] <= 0 or not spans.solves(ctx["spans"], "p2p"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
