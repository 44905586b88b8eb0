"""Reduce a JAX profiler trace (``.xplane.pb``) to intervals on one clock.

What a TPU trace holds (read by hand from a v5e trace of the sim driver):

* one plane ``/device:TPU:<i>`` per chip, with the lines ``XLA Modules``
  (one event per program execution, named ``jit_<fn>(<fingerprint>)``),
  ``XLA Ops`` (one event per HLO instruction run, named by the
  instruction's text, ``%<op>.<k> = <shape> <opcode>(...)``; the ops of a
  loop body nest inside the loop's own event), ``Async XLA Ops`` and
  ``Steps``;
* the plane ``/host:CPU``, one line per host thread; the benchmark's own
  ``TraceAnnotation`` spans sit on the Python thread's line.

Event times are nanoseconds from the start of the trace, the same clock for
host and device planes.  This module only reads and reshapes: the metrics
under ``metrics/`` do the arithmetic.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


def op_base(text: str) -> str:
    """``'%masked_scale_aggregate.1 = f32[...] custom-call(...)'`` ->
    ``'masked_scale_aggregate'``; names without HLO text pass through."""
    m = OP_NAME.match(text)
    return m.group(1) if m else text


def op_label(text: str) -> str:
    """The instruction's own name with its index (``'fusion.138'``)."""
    head = text.split(" = ", 1)[0]
    return head.lstrip("%")


def merge(intervals: list) -> list:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


@dataclass
class DeviceTrace:
    index: int
    ops: list = field(default_factory=list)       # (start, end, text)
    modules: list = field(default_factory=list)   # (start, end, name)
    _starts: list = field(default_factory=list, repr=False)

    def module_at(self, t: float) -> str:
        """Name of the program running at time ``t`` (or '')."""
        if len(self._starts) != len(self.modules):
            self._starts = [m[0] for m in self.modules]
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and self.modules[i][1] >= t:
            return self.modules[i][2]
        return ""


@dataclass
class Trace:
    devices: list                                  # [DeviceTrace], by index
    host: list = field(default_factory=list)       # (start, end, name, thread)

    def annotation(self, name: str) -> tuple:
        """``(start, end)`` of the first host span called ``name``."""
        for s, e, n, _ in self.host:
            if n == name:
                return s, e
        raise KeyError(f"no host span {name!r} in the trace")

    def thread_of(self, name: str) -> str:
        """The host thread that recorded the span called ``name``."""
        for _, _, n, th in self.host:
            if n == name:
                return th
        raise KeyError(f"no host span {name!r} in the trace")

    def host_labeller(self, thread: str):
        """``label(t)``: the innermost span open at ``t`` on ``thread``.

        Spans of one thread nest, so walking back from the last span that
        started by ``t`` the first one still open is the innermost."""
        spans = [(s, e, n) for s, e, n, th in self.host if th == thread]
        starts = [s for s, _, _ in spans]

        def label(t: float) -> str:
            i = bisect.bisect_right(starts, t) - 1
            while i >= 0:
                s, e, n = spans[i]
                if e >= t:
                    return n
                i -= 1
            return ""

        return label


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def reduce_profile(pd) -> Trace:
    """A :class:`Trace` from a ``jax.profiler.ProfileData``."""
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), DeviceTrace(int(m.group(1))))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                                   for e in line.events)
                elif line.name == "XLA Modules":
                    dev.modules.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                                       for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name, line.name)
                            for e in line.events)
    for dev in devices.values():
        dev.ops.sort()
        dev.modules.sort()
    host.sort()
    return Trace([devices[i] for i in sorted(devices)], host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def busy_intervals(dev: DeviceTrace, lo: float, hi: float) -> list:
    """Union of the device's op intervals inside ``[lo, hi]``."""
    return merge(clip([(s, e) for s, e, _ in dev.ops], lo, hi))


def launches(dev: DeviceTrace, lo: float, hi: float) -> int:
    """Program executions that started inside ``[lo, hi]``."""
    return sum(1 for s, _, _ in dev.modules if lo <= s <= hi)


def op_time(dev: DeviceTrace, lo: float, hi: float, match) -> float:
    """Summed device ns of the ops whose text satisfies ``match``, inside
    ``[lo, hi]``.  Nested matches count once (their union)."""
    return covered(merge(clip([(s, e) for s, e, t in dev.ops if match(t)], lo, hi)))


def top_ops(trace: Trace, lo: float, hi: float, k: int = 10) -> list:
    """``[[module/op, seconds], ...]``: the ``k`` ops that took most device
    time, summed over the devices, leaf ops only (a loop's own event spans
    the ops of its body and is left out)."""
    totals = {}
    for dev in trace.devices:
        for s, e, t in dev.ops:
            if e <= lo or s >= hi or " while(" in t or " conditional(" in t:
                continue
            key = f"{dev.module_at(s).split('(')[0]}/{op_label(t)}"
            totals[key] = totals.get(key, 0.0) + (min(e, hi) - max(s, lo))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, lo: float, hi: float, thread: str, k: int = 10) -> list:
    """``[[host span, seconds], ...]``: device 0's idle time inside
    ``[lo, hi]``, summed by the innermost host span open on ``thread`` at
    each gap's midpoint, the ``k`` largest."""
    busy = busy_intervals(trace.devices[0], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    label_at = trace.host_labeller(thread)
    totals = {}
    for s, e in gaps:
        label = label_at((s + e) / 2) or "(none)"
        totals[label] = totals.get(label, 0.0) + (e - s)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in ranked]
