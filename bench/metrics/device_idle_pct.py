"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the chips."""
from bench import traces


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - traces.busy_s(ctx.trace) / traces.window_s(ctx.trace))
