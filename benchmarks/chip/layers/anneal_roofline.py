"""anneal_roofline: the annealer's share of its HBM roofline, in %.

Least bytes of the traced decisions' netcost swap proposals
(``costs.bytes_per_proposal`` times chains times steps of each search; a
search under another objective runs another scan) over the chip's HBM
bandwidth (``peaks.json``), divided by the device time of the
``jit_anneal`` modules that did them."""

from .. import costs


def read(ctx):
    t = ctx.trace
    if t is None or ctx.driver is None:
        return None
    least_bytes = sum(
        costs.bytes_per_proposal(spec) * chains * steps
        for d in ctx.decisions
        for spec, chains, steps, objective in ctx.driver.searches(d)
        if objective == "netcost"
    )
    agg = t["modules"].get("jit_anneal")
    if not least_bytes or not agg or agg["seconds"] <= 0:
        return None
    least_s = least_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / agg["seconds"]
