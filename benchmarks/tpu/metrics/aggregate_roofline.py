"""aggregate_roofline: the Eq. 2 kernels' share of their HBM roofline.

The least time the bytes the algorithm needs could take at the chip's HBM
bandwidth (``peaks.json``), over the summed device time of the aggregate
kernels' events (``tpubench.aggregate``) in the traced window.  The bytes
come from the shapes, per call (``tpubench.costs.aggregate_bytes``): the
``(D,)`` float32 result written, plus the rows the call must read — all its
rows where it also yields norms, only the round's sent clients for a pure
masked aggregate; the entry sums them over the rounds of the window.
Bound by bytes: two FLOPs per element read, far under the chip's ridge.
"""

from tpubench import aggregate


def read(trace, ctx):
    ns = aggregate.kernel_ns(trace, ctx["lo"], ctx["hi"])
    if not ns:
        return None
    least_s = ctx["aggregate_bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
