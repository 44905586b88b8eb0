"""host_cohort_ms_per_round: host ms per round in the program's ``data``
spans — the cohort draw, the clients' example plans, weights, key folds
and the gather's dispatch (per round in prefetch mode, per block in scan
mode) — inside the traced window, over the rounds completed in it.
Layer: the host cohort (``sim/pool.py``, the driver's ``draw_round``).
None where the program has no such span."""

from tpubench import spans


def read(trace, ctx):
    return spans.ms_per_round(spans.union(trace, ("data",), ctx["lo"], ctx["hi"]),
                              ctx["rounds"])
