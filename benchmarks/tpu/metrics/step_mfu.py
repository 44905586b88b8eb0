"""step_mfu: the whole round step's share of the chips' bf16 peak.

Model FLOPs of a round (the configuration's ``6 x parameters x samples or
tokens``, rematerialisation not counted) times the rounds completed in the
traced window, over the window's length, over ``chips x peak bf16 FLOP/s``
from ``peaks.json``.
"""


def read(trace, ctx):
    if not ctx["rounds"]:
        return None
    flops = ctx["flops_per_round"] * ctx["rounds"]
    return 100.0 * flops / ctx["window_s"] / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
