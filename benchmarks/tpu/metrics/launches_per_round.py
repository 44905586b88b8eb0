"""launches_per_round: program executions per round on a chip.

Device program executions (``XLA Modules`` events) that started inside the
traced window, on the chip that launched most, over the rounds completed in
the window.  Layer: the sim driver (and the engine loop), whose per-round
dispatch each launch is.
"""

from tpubench import trace_reduce


def read(trace, ctx):
    n = max(trace_reduce.launches(d, ctx["lo"], ctx["hi"]) for d in trace.devices)
    return n / ctx["rounds"] if n else None
