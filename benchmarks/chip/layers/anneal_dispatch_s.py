"""anneal_dispatch_s: seconds per decision the host spends handing the
annealer's scan to the device (``anneal.dispatch``: proposal draws,
thresholds, ledger and the jitted calls up to their return)."""

from .. import spans


def read(ctx, log=None):
    return spans.seconds_per_decision(ctx.traced_decisions, ("anneal.dispatch",), log)
