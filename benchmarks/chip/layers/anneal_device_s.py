"""anneal_device_s: device seconds of the annealer's jitted scan
(``jit_anneal`` modules) per decision."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced_decisions:
        return None
    agg = t["modules"].get("jit_anneal")
    if not agg or agg["seconds"] <= 0:
        return None
    return agg["seconds"] / ctx.traced_decisions
