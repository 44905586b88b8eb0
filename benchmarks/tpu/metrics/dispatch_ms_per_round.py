"""dispatch_ms_per_round: host ms per round in the program's ``round``
spans (the dispatch of the round step, or of a scan block), less the
``compile`` span nested in the call's first one (tracing, lowering and the
compile-cache load), inside the traced window, over the rounds completed
in it.  Layer: the sim driver.  None where the program has no such span."""

from tpubench import spans


def read(trace, ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    rounds = spans.union(trace, ("round",), lo, hi)
    return spans.ms_per_round(spans.minus(rounds, spans.union(trace, ("compile",), lo, hi)),
                              ctx["rounds"])
