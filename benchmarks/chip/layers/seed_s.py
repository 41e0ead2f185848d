"""seed_s: seconds per decision of the search's host seeding: the greedy
descent (``search.seed``) and the arena and re-seeds (``search.inits``)."""

from .. import spans


def read(ctx, log=None):
    return spans.seconds_per_decision(ctx.traced_decisions, ("search.seed", "search.inits"), log)
