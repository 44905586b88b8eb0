"""pool_build_s: host seconds the sim driver's call spends making its
client pool — the union of the program's ``pool_build`` (padding every
client's data into one host buffer per key) and ``pool_upload`` (dispatching
the buffers' copies to the device) spans inside the traced window.  The
copies run asynchronously: what of them the host waits for falls in the
call's ``first_sync`` span, which this does not read.  Layer: the host
cohort (``sim/pool.py::ClientPool``).  None where the program has no such
span."""

from tpubench import spans, trace_reduce


def read(trace, ctx):
    ns = trace_reduce.covered(
        spans.union(trace, ("pool_build", "pool_upload"), ctx["lo"], ctx["hi"]))
    return ns / 1e9 if ns else None
