"""Device busy time of the traced interval over the sweeps that the batch
solve spans in it report, ms per sweep."""
from bench import spans


def read(ctx):
    tr = ctx["trace"]
    sweeps = sum(sp.args.get("sweeps", 0)
                 for sp in spans.solves(ctx["spans"], "rows"))
    if not tr or not sweeps or tr["busy_s"] <= 0:
        return None
    return tr["busy_s"] / sweeps * 1e3
