"""Share of the traced window in which the device was idle while the
main thread was inside the train step's call (the program's
``train.dispatch`` spans, put on the trace's clock by ``spans.py``),
averaged over the chips. Part of ``device_idle_pct``, never above it."""
from bench import spans, traces


def read(ctx):
    rec = spans.recorder()
    if rec is None or ctx.trace is None or not ctx.trace.devices:
        return None
    got = spans.dispatch_on_trace(rec, ctx.trace, ctx.traced_steps)
    if got is None:
        return None
    lo, hi = ctx.trace.window()
    inside = traces.union(got, lo, hi)
    per = [traces.subtract(inside, traces.union(((s, e) for _, s, e in ops),
                                                lo, hi))
           for ops in ctx.trace.devices.values()]
    return 100.0 * sum(per) / len(per) / (hi - lo)
