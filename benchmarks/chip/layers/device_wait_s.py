"""device_wait_s: seconds per decision the host waits on the device's
results (every ``device.wait``) and copies them back (``device.fetch``)."""

from .. import spans


def read(ctx, log=None):
    return spans.seconds_per_decision(ctx.traced_decisions, ("device.wait", "device.fetch"), log)
