"""api_self_s: seconds per decision in the Nimbus call itself (the root
``nimbus.plan`` / ``nimbus.submit`` span) less its ``nimbus.schedule``
child: payload checks, cluster build, commit and the returned plan."""

from .. import spans


def read(ctx, log=None):
    found = spans.decisions(ctx.traced_decisions, log)
    if found is None:
        return None
    own = 0.0
    for root, tree in found:
        scheduled = sum(
            sp.wall_s for sp in tree if sp.parent == root.seq and sp.name == "nimbus.schedule"
        )
        own += root.wall_s - scheduled
    return own / ctx.traced_decisions
