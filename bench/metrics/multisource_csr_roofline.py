"""Share of the HBM roofline reached by the traced batch solves: the least
bytes any exact solve of their rows must move (``roofline.py``) at the
device's peak bandwidth, over the device time of their
``sssp_multisource_csr`` programs in the trace, %."""
from bench import roofline, spans


def read(ctx):
    tr, solves = ctx["trace"], spans.solves(ctx["spans"], "rows")
    if not tr or not solves:
        return None
    device_s = sum(s for name, s in tr["modules"]
                   if "sssp_multisource_csr" in name)
    if device_s <= 0:
        return None
    g = ctx["graph"]
    least = sum(roofline.batch_least_bytes(
        g["n"], g["arcs"], round(sp.args["B"] * sp.args["occupancy"]))
        for sp in solves)
    bw = roofline.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / bw / device_s
