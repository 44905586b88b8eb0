"""aggregate_ms_per_round: device time of the Eq. 2 kernels per round.

The summed device time of the aggregate kernels' events
(``tpubench.aggregate``) inside the traced window, averaged over the cell's
chips, per round completed in the window.
"""

from tpubench import aggregate


def read(trace, ctx):
    ns = aggregate.kernel_ns(trace, ctx["lo"], ctx["hi"])
    if not ns or not ctx["rounds"]:
        return None
    return ns / 1e6 / len(trace.devices) / ctx["rounds"]
