"""Mean ``sweeps`` payload of the traced p2p solve spans."""
from bench import spans


def read(ctx):
    s = [sp.args["sweeps"] for sp in spans.solves(ctx["spans"], "p2p")
         if "sweeps" in sp.args]
    return sum(s) / len(s) if s else None
