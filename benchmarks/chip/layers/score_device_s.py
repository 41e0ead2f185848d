"""score_device_s: device seconds of the batched scorers (``jit_evaluate``
modules: network cost and validity of a batch of candidates) per decision."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced_decisions:
        return None
    agg = t["modules"].get("jit_evaluate")
    if not agg or agg["seconds"] <= 0:
        return None
    return agg["seconds"] / ctx.traced_decisions
