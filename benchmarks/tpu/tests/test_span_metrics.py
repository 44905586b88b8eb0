"""The five readers of the program's host spans (``tpubench.spans``) on a
hand-built trace whose every number is known."""

import pytest

from tpubench import harness, spans, trace_reduce

W = "bench:window"


def _metric(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py", f"m_{name}")


def _p(name):
    return spans.PREFIX + name


def trace():
    """Window [1000, 11000] ns on thread ``py``, two rounds.  Inside it: the
    pool build 1000-2000 and upload 2000-2500; round 0's data 2500-3000
    (plan 2500-2800, gather 2800-3000) and round 3000-5000 with a compile
    3000-4500 nested; round 1's data 5000-5400 and round 5400-5600;
    the ledger 9000-10000, holding a device read 9000-9500.  Outside it:
    a set-up span 0-900 and a data span 11000-11500.  On thread ``other``:
    a data span 6000-8000.  Device 0 busy 4500-5200 and 5600-6000; device
    1 (never read here) busy 1000-11000."""
    host = [
        (0, 900, _p("setup"), "py"),
        (1000, 11000, W, "py"),
        (1000, 2000, _p("pool_build"), "py"),
        (2000, 2500, _p("pool_upload"), "py"),
        (2500, 3000, _p("data"), "py"),
        (2500, 2800, _p("plan"), "py"),
        (2800, 3000, _p("gather"), "py"),
        (3000, 5000, _p("round"), "py"),
        (3000, 4500, _p("compile"), "py"),
        (5000, 5400, _p("data"), "py"),
        (5400, 5600, _p("round"), "py"),
        (6000, 8000, _p("data"), "other"),
        (9000, 10000, _p("ledger"), "py"),
        (9000, 9500, "np.asarray(jax.Array)", "py"),
        (11000, 11500, _p("data"), "py"),
    ]
    dev0 = trace_reduce.DeviceTrace(0, ops=[(4500, 5200, "%fusion.1 = f32[8] fusion()"),
                                            (5600, 6000, "%fusion.2 = f32[8] fusion()")])
    dev1 = trace_reduce.DeviceTrace(1, ops=[(1000, 11000, "%fusion.3 = f32[8] fusion()")])
    return trace_reduce.Trace([dev0, dev1], sorted(host))


def _ctx(tr, rounds=2):
    lo, hi = tr.annotation(W)
    return dict(lo=lo, hi=hi, window_s=(hi - lo) / 1e9, rounds=rounds, chips=2)


def test_minus():
    assert spans.minus([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert spans.minus([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert spans.minus([(0, 4)], []) == [(0, 4)]
    assert spans.minus([(2, 3)], [(0, 10)]) == []


def test_union_keeps_the_window_thread_and_clips():
    tr = trace()
    lo, hi = tr.annotation(W)
    # the data span on the other thread and the one after the window drop out
    assert spans.union(tr, ("data",), lo, hi) == [(2500, 3000), (5000, 5400)]
    assert spans.union(tr, ("pool_build", "pool_upload"), lo, hi) == [(1000, 2500)]
    assert spans.union(tr, ("setup",), lo, hi) == []
    assert spans.union(tr, ("round",), 4000, hi) == [(4000, 5000), (5400, 5600)]


@pytest.mark.parametrize("name,want", [
    ("pool_build_s", 1500 / 1e9),
    ("host_cohort_ms_per_round", (500 + 400) / 1e6 / 2),
    # the rounds' 2200 ns less the 1500 ns compile nested in the first
    ("dispatch_ms_per_round", (2200 - 1500) / 1e6 / 2),
    ("ledger_ms_per_round", 1000 / 1e6 / 2),
    # device 0 idles 1000-4500, 5200-5600 and 6000-11000 (8900 ns); no
    # span but the window is open 5600-9000 and 10000-11000, of which it
    # idles 6000-9000 and 10000-11000 (4000 ns)
    ("idle_unnamed_pct", 100.0 * 4000 / 8900),
])
def test_span_metric(name, want):
    tr = trace()
    assert _metric(name).read(tr, _ctx(tr)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["pool_build_s", "host_cohort_ms_per_round",
                                  "dispatch_ms_per_round", "ledger_ms_per_round"])
def test_no_program_span_reads_nothing(name):
    """A program without ``repro.obs`` spans (the benchmark's parent) gives
    None, and all of device 0's idle time is unnamed."""
    tr = trace()
    tr.host = [h for h in tr.host if not h[2].startswith(spans.PREFIX)]
    ctx = _ctx(tr)
    assert _metric(name).read(tr, ctx) is None
    # the device read inside the ledger is still a named span: 8900 ns
    # idle, 500 of them under np.asarray
    assert _metric("idle_unnamed_pct").read(tr, ctx) == pytest.approx(100.0 * 8400 / 8900)


def test_idle_unnamed_without_idle():
    tr = trace()
    tr.devices[0] = tr.devices[1]
    assert _metric("idle_unnamed_pct").read(tr, _ctx(tr)) == 0.0
