"""idle_unnamed_pct: the share of device 0's idle time in the traced
window during which no host span is open on the window's thread but the
window itself — the idle time that no span names, which the breakdown's
``idle_gaps`` shows as ``bench:window``.  Measured over time, not at the
gaps' midpoints.  0 where the device never idles."""

from tpubench import spans, trace_reduce


def read(trace, ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    idle = spans.minus([(lo, hi)], trace_reduce.busy_intervals(trace.devices[0], lo, hi))
    total = trace_reduce.covered(idle)
    if not total:
        return 0.0
    unnamed = spans.minus(idle, spans.named(trace, lo, hi))
    return 100.0 * trace_reduce.covered(unnamed) / total
