"""device_idle_pct: the share of the traced window in which no operation
ran on the device — 1 minus the union of the device's op intervals over
the window's length, averaged over the cell's chips."""

from tpubench import trace_reduce


def read(trace, ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    busy = [trace_reduce.covered(trace_reduce.busy_intervals(d, lo, hi))
            for d in trace.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
