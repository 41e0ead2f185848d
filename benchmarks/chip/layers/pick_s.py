"""pick_s: seconds per decision of the search's winner pick, decode and
re-placing of stranded tasks (``search.pick``)."""

from .. import spans


def read(ctx, log=None):
    return spans.seconds_per_decision(ctx.traced_decisions, ("search.pick",), log)
