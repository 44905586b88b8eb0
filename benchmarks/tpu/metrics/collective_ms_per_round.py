"""collective_ms_per_round: device time of the cross-chip collectives per
round — the ops named all-reduce, reduce-scatter, all-gather, all-to-all or
collective-permute (and their start/done halves) inside the traced window,
on the chip that spent most, per round.  None where no collective ran."""

from tpubench import trace_reduce

PREFIXES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
            "collective-permute")


def is_collective(text: str) -> bool:
    return trace_reduce.op_base(text).startswith(PREFIXES)


def read(trace, ctx):
    ns = max(trace_reduce.op_time(d, ctx["lo"], ctx["hi"], is_collective)
             for d in trace.devices)
    if not ns or not ctx["rounds"]:
        return None
    return ns / 1e6 / ctx["rounds"]
