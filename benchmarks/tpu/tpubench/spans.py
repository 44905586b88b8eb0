"""The program's own host spans in a reduced trace, shared by the metrics
that read them.

The program names its host work with ``repro.obs/<name>`` annotations
(``src/repro/obs/trace.py::span``), opened on the thread that calls it, so
inside the benchmark's ``bench:window`` on that thread's line of the host
plane, and properly nested.  A program without those spans gives empty
unions here, and the metrics that read them return None.
"""

from __future__ import annotations

from tpubench import trace_reduce

PREFIX = "repro.obs/"
WINDOW = "bench:window"


def _window_thread(trace) -> list:
    thread = trace.thread_of(WINDOW)
    return [(s, e, n) for s, e, n, th in trace.host if th == thread]


def union(trace, names, lo: float, hi: float) -> list:
    """Union of the program spans called one of ``names`` (without the
    prefix) on the window's thread, clipped to ``[lo, hi]``."""
    want = {PREFIX + n for n in names}
    return trace_reduce.merge(trace_reduce.clip(
        [(s, e) for s, e, n in _window_thread(trace) if n in want], lo, hi))


def named(trace, lo: float, hi: float) -> list:
    """Union of every host span on the window's thread inside ``[lo, hi]``
    but the ones that hold all of it (the window itself): where the
    innermost open span is something other than the bare window."""
    return trace_reduce.merge(trace_reduce.clip(
        [(s, e) for s, e, _ in _window_thread(trace) if not (s <= lo and e >= hi)],
        lo, hi))


def minus(a: list, b: list) -> list:
    """The parts of the intervals ``a`` outside the intervals ``b``; both
    sorted and disjoint, as ``trace_reduce.merge`` gives them."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def ms_per_round(intervals: list, rounds: int):
    """Covered ms per round of the window, or None where nothing was read."""
    ns = trace_reduce.covered(intervals)
    if not ns or not rounds:
        return None
    return ns / 1e6 / rounds
