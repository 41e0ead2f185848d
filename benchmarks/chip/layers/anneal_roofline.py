"""anneal_roofline: the annealer's share of its HBM roofline, in %.

Least bytes of the traced decisions' swap proposals (``costs.anneal_bytes``)
over the chip's HBM bandwidth (``peaks.json``), divided by the device time
of the ``jit_anneal`` modules that did them."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.anneal_bytes:
        return None
    agg = t["modules"].get("jit_anneal")
    if not agg or agg["seconds"] <= 0:
        return None
    least_s = ctx.anneal_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / agg["seconds"]
