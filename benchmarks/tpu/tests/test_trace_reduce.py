"""The reduction from a profiler trace to the per-layer metrics: a synthetic
trace whose every number is known, and a small trace recorded on a TPU v5e
(femnist-prefetch, a few rounds)."""

from pathlib import Path

import pytest

from tpubench import aggregate, harness, trace_reduce

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "femnist_prefetch.xplane.pb"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _event(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _device(idx, ops, modules):
    names = sorted({n for n, _, _ in ops} | {n for n, _, _ in modules})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                     for n, i in ids.items())
    op_ev = "\n".join(_event(ids[n], s, d) for n, s, d in ops)
    mod_ev = "\n".join(_event(ids[n], s, d) for n, s, d in modules)
    return f'''planes {{ id: {idx + 1} name: "/device:TPU:{idx}"
      lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {op_ev} }}
      lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {mod_ev} }}
      {meta} }}'''


AGG = "%masked_scale_aggregate.1 = f32[1,128] custom-call(f32[1,8] %s, f32[8,128] %x)"
FUSION = "%fusion.7 = f32[8] fusion(f32[8] %p)"
LOOP = "%while.3 = (s32[]) while((s32[]) %t)"
PSUM = "%all-reduce.2 = f32[128] all-reduce(f32[128] %a)"


def synthetic():
    """Window [1000, 11000] ns.  Device 0: a loop 1000-5000 holding a fusion
    1500-2500 and the kernel 3000-4000, a psum 6000-7000; device 1: the
    kernel 2000-3000 and a psum 6000-9000.  Three launches on device 0, one
    before the window; two on device 1."""
    from jax.profiler import ProfileData

    dev0 = _device(0, [(LOOP, 1000, 4000), (FUSION, 1500, 1000), (AGG, 3000, 1000),
                       (PSUM, 6000, 1000)],
                   [("jit_a(1)", 500, 300), ("jit_b(2)", 1000, 4000), ("jit_c(3)", 6000, 1000)])
    dev1 = _device(1, [(AGG, 2000, 1000), (PSUM, 6000, 3000)],
                   [("jit_b(2)", 2000, 1000), ("jit_c(3)", 6000, 3000)])
    host = '''planes { id: 9 name: "/host:CPU"
      lines { id: 1 name: "python3" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
        events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000 } }
      event_metadata { key: 1 value { id: 1 name: "bench:window" } }
      event_metadata { key: 2 value { id: 2 name: "bench:wait" } } }'''
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(dev0 + dev1 + host))


def _ctx(trace, rounds=2, chips=2, **kw):
    lo, hi = trace.annotation("bench:window")
    return dict(lo=lo, hi=hi, window_s=(hi - lo) / 1e9, rounds=rounds, chips=chips,
                peaks=PEAKS, **kw)


def _metric(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py", f"m_{name}")


def test_synthetic_intervals():
    tr = synthetic()
    lo, hi = tr.annotation("bench:window")
    assert (lo, hi) == (1000, 11000)
    d0, d1 = tr.devices
    assert trace_reduce.busy_intervals(d0, lo, hi) == [(1000, 5000), (6000, 7000)]
    assert trace_reduce.launches(d0, lo, hi) == 2 and trace_reduce.launches(d1, lo, hi) == 2
    assert aggregate.kernel_ns(tr, lo, hi) == 2000
    assert d0.module_at(3500) == "jit_b(2)"
    assert trace_reduce.op_base(AGG) == "masked_scale_aggregate"
    assert trace_reduce.op_base(PSUM) == "all-reduce"


def test_synthetic_metrics():
    tr = synthetic()
    ctx = _ctx(tr, aggregate_bytes=819e9 * 1e-6, flops_per_round=197e12 * 1e-6)
    # busy: device 0 5000 ns, device 1 4000 ns of a 10000 ns window
    assert _metric("device_idle_pct").read(tr, ctx) == pytest.approx(55.0)
    assert _metric("launches_per_round").read(tr, ctx) == 1.0
    assert _metric("aggregate_ms_per_round").read(tr, ctx) == pytest.approx(2000 / 1e6 / 2 / 2)
    # 1 us of least time over 2 us of kernel time
    assert _metric("aggregate_roofline").read(tr, ctx) == pytest.approx(50.0)
    # 2 rounds x 1 us of peak work over a 10 us window on 2 chips
    assert _metric("step_mfu").read(tr, ctx) == pytest.approx(10.0)
    # the chip that spent most: device 1, 3000 ns over 2 rounds
    assert _metric("collective_ms_per_round").read(tr, ctx) == pytest.approx(0.0015)


def test_synthetic_breakdown():
    tr = synthetic()
    lo, hi = tr.annotation("bench:window")
    top = dict(trace_reduce.top_ops(tr, lo, hi))
    assert top == {"jit_c/all-reduce.2": pytest.approx(4000 / 1e9),
                   "jit_b/masked_scale_aggregate.1": pytest.approx(2000 / 1e9),
                   "jit_b/fusion.7": pytest.approx(1000 / 1e9)}
    gaps = dict(trace_reduce.idle_gaps(tr, lo, hi, "python3"))
    # device 0 idles 5000-6000 (host waits) and 7000-11000 (host in the window)
    assert gaps == {"bench:window": pytest.approx(4000 / 1e9),
                    "bench:wait": pytest.approx(1000 / 1e9)}


def test_no_kernel_no_collective_reads_nothing():
    tr = synthetic()
    ctx = _ctx(tr, aggregate_bytes=1.0, flops_per_round=1.0)
    ctx["lo"], ctx["hi"] = 7500, 7600       # only device 1's psum runs here
    assert _metric("aggregate_roofline").read(tr, ctx) is None
    assert _metric("aggregate_ms_per_round").read(tr, ctx) is None
    ctx["lo"], ctx["hi"] = 9500, 11000       # nothing runs here
    assert _metric("collective_ms_per_round").read(tr, ctx) is None


@pytest.mark.skipif(not FIXTURE.is_file(), reason="recorded trace not present")
def test_recorded_trace():
    tr = trace_reduce.load(str(FIXTURE))
    lo, hi = tr.annotation("bench:window")
    assert len(tr.devices) == 1
    ctx = _ctx(tr, rounds=4, chips=1, aggregate_bytes=4 * 58_430 * 4 * 4,
               flops_per_round=1.79e9)
    idle = _metric("device_idle_pct").read(tr, ctx)
    assert 0.0 < idle < 100.0
    assert 3.0 <= _metric("launches_per_round").read(tr, ctx) <= 8.0
    assert _metric("aggregate_ms_per_round").read(tr, ctx) > 0
    assert 0.0 < _metric("aggregate_roofline").read(tr, ctx) <= 100.0
    assert _metric("collective_ms_per_round").read(tr, ctx) is None
    names = [n for n, _ in trace_reduce.top_ops(tr, lo, hi)]
    assert any(n.startswith("jit_round_step/") for n in names)
