"""Phase spans: monotonic wall-time measurement + profiler trace annotation.

:func:`span` is the one timing primitive of the obs layer — a context
manager that (a) opens a ``jax.profiler.TraceAnnotation`` named
``repro.obs/<name>`` so the phase shows up as a named slice in
TensorBoard/Perfetto dumps and on the host plane of a ``.xplane.pb``, on the
same clock as the device's ops, and (b) records the phase's wall time on
the monotonic clock (``time.perf_counter`` — never ``time.time``, which NTP
can step backwards mid-run).  Because JAX dispatch is asynchronous, a naive
exit timestamp would measure *enqueue* time only; a span that records into
a sink therefore takes a ``block(x)`` target whose arrays are
``jax.block_until_ready``-waited before the clock stops, so the recorded
seconds bound the device work of the phase, not just its dispatch.  A span
with no sink records nowhere and never waits: it only annotates, so the
sim driver keeps its spans open on the hot path whether telemetry is on or
off, and the results stay bitwise the same.

:class:`TraceWindow` is the ``--trace-dir`` support: it wraps the first N
rounds of a run in ``jax.profiler.start_trace`` / ``stop_trace`` so a
TensorBoard/Perfetto trace of representative steady-state rounds lands on
disk without instrumenting the whole (possibly hours-long) run.
"""

from __future__ import annotations

import contextlib
import time

import jax

# the five phases of one communication round — the contract names
# span()/Telemetry publish and the obs-smoke CI step asserts.  (Execution
# order is local_update -> compress -> sample -> aggregate -> server_opt:
# the plan needs the norms of what clients would send.)
PHASES = ("sample", "local_update", "compress", "aggregate", "server_opt")


class Span:
    """One timed phase: ``name``, a block target, and the measured seconds."""

    __slots__ = ("name", "seconds", "_block")

    def __init__(self, name: str):
        self.name = name
        self.seconds = 0.0
        self._block = None

    def block(self, arrays) -> None:
        """Arrays to ``jax.block_until_ready`` before the span closes, so the
        recorded wall time covers the phase's device work.  Only a span with
        a sink waits; one that records nowhere ignores the target."""
        self._block = arrays


@contextlib.contextmanager
def span(name: str, sink=None):
    """Time one phase on the monotonic clock, annotated for the profiler.

    Yields a :class:`Span`.  ``sink`` (a
    :class:`~repro.obs.telemetry.Telemetry`, or anything with
    ``record_span(name, seconds)``) receives the measurement, and then
    ``sp.block(arrays)`` makes the phase's device work
    ``block_until_ready``-bounded before the clock stops.  With
    ``sink=None`` the span only opens the ``repro.obs/<name>`` annotation
    and reads the clock (``sp.seconds`` is the dispatch time): it never
    syncs the device.  The wall time is ``time.perf_counter`` based —
    monotonic, so committed baselines cannot be corrupted by NTP steps.
    """
    sp = Span(name)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"repro.obs/{name}"):
        try:
            yield sp
        finally:
            if sink is not None and sp._block is not None:
                jax.block_until_ready(sp._block)
            sp.seconds = time.perf_counter() - t0
            if sink is not None:
                sink.record_span(name, sp.seconds)


class TraceWindow:
    """``--trace-dir`` support: profile the first ``rounds`` rounds to disk.

    ``round_start(k)`` opens ``jax.profiler.start_trace(trace_dir)`` at round
    0; ``round_end(k)`` stops it once ``rounds`` rounds have completed (and
    :meth:`close` stops it unconditionally, so a short run still flushes a
    valid trace).  View with TensorBoard's profile plugin or by loading the
    ``.trace.json.gz`` into Perfetto — each obs phase appears as a
    ``repro.obs/<phase>`` slice via :func:`span`'s TraceAnnotation.
    """

    def __init__(self, trace_dir: str | None, rounds: int = 3):
        if rounds < 1:
            raise ValueError(f"trace window must cover >= 1 round, got {rounds}")
        self.trace_dir = trace_dir
        self.rounds = rounds
        self.active = False

    def round_start(self, k: int) -> None:
        """Open the profiler trace when round ``k`` is the window's first."""
        if self.trace_dir is not None and k == 0 and not self.active:
            jax.profiler.start_trace(self.trace_dir)
            self.active = True

    def round_end(self, k: int) -> None:
        """Close the trace once the window's last round has completed."""
        if self.active and k + 1 >= self.rounds:
            jax.profiler.stop_trace()
            self.active = False

    def close(self) -> None:
        """Stop an in-flight trace (runs shorter than the window)."""
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
