"""Which trace events are the Eq. 2 aggregate kernels, shared by the two
aggregate metrics.

The Pallas kernels carry no ``name=``; in a v5e trace they are custom
calls named after their jitted wrappers — ``masked_scale_aggregate``
(vmap engine), ``norm_scale_aggregate`` (scan engine), and the
``shard_``/``sharded_`` forms on the client mesh — so every custom call
whose name holds ``agg`` is one of them.
"""

from tpubench import trace_reduce


def is_aggregate_kernel(text: str) -> bool:
    return "custom-call(" in text and "agg" in trace_reduce.op_base(text)


def kernel_ns(trace, lo: float, hi: float) -> float:
    """Their device ns inside ``[lo, hi]``, summed over the chips."""
    return sum(trace_reduce.op_time(d, lo, hi, is_aggregate_kernel)
               for d in trace.devices)
