"""ledger_ms_per_round: host ms per round in the program's ``ledger`` span
(after the call's last round: the per-round device reads and the host
arithmetic of the ledger) inside the traced window, over the rounds
completed in it.  Layer: the sim driver.  None where the program has no
such span."""

from tpubench import spans


def read(trace, ctx):
    return spans.ms_per_round(spans.union(trace, ("ledger",), ctx["lo"], ctx["hi"]),
                              ctx["rounds"])
