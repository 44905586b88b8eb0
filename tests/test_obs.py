"""Observability layer (repro.obs): phase spans, the JSONL event stream,
the live metrics endpoint, the phased executor, and the online Eq. 2 gap
estimator — including the subsystem's two acceptance gates:

- the gap is **exactly zero** at full participation (the `full` sampler's
  plan scale is bitwise ``w_i``, so the sampled and full-participation
  aggregates run the identical computation), in vmap AND scan engines;
- telemetry off (or on with ``phases=False``) changes **nothing** the
  ledger records beyond wall clock and the sparse gap series — the
  schema-3 ledger is byte-identical minus those fields.
"""

import glob
import json
import os
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.data import femnist_like
from repro.fl.engine import RoundEngine
from repro.fl.round import client_weights
from repro.models.simple import mlp_classifier
from repro.obs import (
    OBS_SCHEMA,
    EventLog,
    MetricsServer,
    ObsConfig,
    Telemetry,
    flat_gap_stats,
    gap_ratio,
    get_logger,
    render_prometheus,
    span,
    tree_gap_stats,
)
from repro.obs.events import read_events
from repro.obs.phased import make_phased_step
from repro.obs.trace import PHASES
from repro.sim import run_scenario, run_simulation, validate_ledger


@pytest.fixture(scope="module")
def small_ds():
    return femnist_like(
        dataset_id=1, n_clients=16, dim=32, num_classes=10, base_examples=16,
        seed=0,
    )


def _strip_obs(doc):
    """Ledger JSON minus everything telemetry is allowed to affect: the
    wall-clock fields and the sparse gap series (present only when the gap
    estimator ran).  What remains must be byte-identical with telemetry on
    and off — the subsystem's zero-interference gate."""
    doc = json.loads(json.dumps(doc))
    doc.pop("wall_s", None)
    doc.pop("rounds_per_sec", None)
    for k in ("wall_ms", "gap_rounds", "gap_sq", "gap_full_sq", "gap_ratio"):
        doc.get("metrics", {}).pop(k, None)
    return doc


# --- spans + sinks ---------------------------------------------------------

def test_span_times_and_records():
    class Sink:
        def __init__(self):
            self.got = []

        def record_span(self, name, seconds):
            self.got.append((name, seconds))

    sink = Sink()
    with span("aggregate", sink) as sp:
        time.sleep(0.01)
        sp.block(jnp.zeros(3))
    assert sp.seconds >= 0.01
    assert sink.got and sink.got[0][0] == "aggregate"
    assert sink.got[0][1] == sp.seconds
    # sink-less spans still time (the driver's obs=None null path)
    with span("sample") as sp2:
        pass
    assert sp2.seconds >= 0.0


@pytest.mark.parametrize("with_sink", [False, True], ids=["no-sink", "sink"])
def test_span_blocks_only_with_a_sink(monkeypatch, with_sink):
    """A span that records nowhere never syncs the device: its block target
    is waited on only when a sink receives the measurement."""
    waits = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: waits.append(x) or x)

    class Sink:
        def record_span(self, name, seconds):
            pass

    with span("round", Sink() if with_sink else None) as sp:
        sp.block(jnp.zeros(3))
    assert len(waits) == (1 if with_sink else 0)
    assert sp.seconds >= 0.0


def _host_spans(log_dir):
    """``[(start_ns, end_ns, name)]`` of the ``repro.obs/`` annotations on the
    host thread that recorded them, from the profiler's ``.xplane.pb``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    lines = [line for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines]
    per_line = [[(e.start_ns, e.start_ns + e.duration_ns, e.name[len("repro.obs/"):])
                 for e in line.events if e.name.startswith("repro.obs/")]
                for line in lines]
    (spans,) = [sp for sp in per_line if sp]     # one thread: the caller's
    return sorted(spans, key=lambda t: (t[0], -t[1]))


def _inside(outer, inner):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.mark.parametrize("mode,units", [("prefetch", 4), ("scan", 2)])
def test_telemetry_off_spans_in_the_trace(small_ds, tmp_path, mode, units):
    """With telemetry off the driver still names its host work in the
    profiler trace, on the calling thread and properly nested: the call's
    pool build and upload, set-up, first sync and ledger once, the ledger
    holding its one device read ``ledger_read``; ``data`` (holding one
    ``plan`` and one ``gather``) and ``round`` once per round (prefetch) or
    per block (scan); one ``compile`` inside the first ``round``."""
    init, loss, _ = mlp_classifier(small_ds.input_dim, small_ds.num_classes,
                                   hidden=8)
    fl = FLConfig(n_clients=4, expected_clients=2, local_steps=2, lr_local=0.1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run_simulation(small_ds, init, loss, fl, 4, batch_size=4, mode=mode,
                       rounds_per_scan=2)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by_name = {}
    for sp_ in spans:
        by_name.setdefault(sp_[2], []).append(sp_)
    counts = {n: len(v) for n, v in by_name.items()}
    assert counts == {"setup": 1, "pool_build": 1, "pool_upload": 1, "ledger": 1,
                      "ledger_read": 1, "first_sync": 1, "data": units,
                      "plan": units, "gather": units, "round": units, "compile": 1}
    # spans of one thread nest: any two are disjoint or one holds the other
    for i, a in enumerate(spans):
        for b in spans[i + 1:]:
            assert b[0] >= a[1] or _inside(a, b), (a, b)
    for d in by_name["data"]:
        inner = [s for s in spans if s is not d and _inside(d, s)]
        assert sorted(s[2] for s in inner) == ["gather", "plan"]
    assert _inside(by_name["round"][0], by_name["compile"][0])
    assert _inside(by_name["ledger"][0], by_name["ledger_read"][0])
    # set-up, pool, rounds and ledger follow one another in the call; the
    # first sync ends the first round
    order = [s[2] for s in spans if s[2] in ("setup", "pool_build", "pool_upload",
                                             "round", "first_sync", "ledger")]
    assert order == (["setup", "pool_build", "pool_upload", "round", "first_sync"]
                     + ["round"] * (units - 1) + ["ledger"])


def test_host_mode_spans_in_the_trace(small_ds, tmp_path):
    """Host mode names its host work like the pool modes, without a pool:
    set-up, compile and ledger (holding ``ledger_read``) once, ``data`` and
    ``round`` once per round, each round after its own ``data``; no
    ``pool_build``, ``pool_upload``, ``plan``, ``gather`` or ``first_sync``
    (the host loop syncs every round)."""
    init, loss, _ = mlp_classifier(small_ds.input_dim, small_ds.num_classes,
                                   hidden=8)
    fl = FLConfig(n_clients=4, expected_clients=2, local_steps=2, lr_local=0.1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run_simulation(small_ds, init, loss, fl, 4, batch_size=4, mode="host")
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    counts = {}
    for sp_ in spans:
        counts[sp_[2]] = counts.get(sp_[2], 0) + 1
    assert counts == {"setup": 1, "ledger": 1, "ledger_read": 1, "data": 4,
                      "round": 4, "compile": 1}
    for i, a in enumerate(spans):
        for b in spans[i + 1:]:
            assert b[0] >= a[1] or _inside(a, b), (a, b)
    first_round = next(s for s in spans if s[2] == "round")
    assert _inside(first_round, next(s for s in spans if s[2] == "compile"))
    order = [s[2] for s in spans if s[2] != "ledger_read"]
    assert order == (["setup", "data", "round", "compile"]
                     + ["data", "round"] * 3 + ["ledger"])


def test_arch_loop_spans_in_the_trace(tmp_path, monkeypatch):
    """``launch/train.py``'s arch loop names its host work as the sim
    driver does: ``setup`` once, then per round ``data``, ``round`` (the
    first holding the one ``compile``) and ``ledger``, in that order; its
    round events count the clients whose update norm is not finite."""
    from repro.launch import train

    # train.main turns the persistent compile cache on unless this variable
    # is set; set after jax read it, it keeps this test process uncached
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    events = str(tmp_path / "events.jsonl")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        train.main(["--arch", "mamba2-130m-reduced", "--rounds", "2", "--clients", "4",
                    "--expected", "2", "--batch", "1", "--seq", "32",
                    "--engine", "scan", "--scan-group", "2", "--cache-groups", "2",
                    "--agg-backend", "pallas", "--obs-jsonl", events])
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path / "trace"))
    counts = {}
    for sp_ in spans:
        counts[sp_[2]] = counts.get(sp_[2], 0) + 1
    assert counts == {"setup": 1, "data": 2, "round": 2, "compile": 1, "ledger": 2}
    (compile_,) = [s for s in spans if s[2] == "compile"]
    first_round = next(s for s in spans if s[2] == "round")
    assert _inside(first_round, compile_)
    order = [s[2] for s in spans if s[2] != "compile"]
    assert order == ["setup"] + ["data", "round", "ledger"] * 2
    rounds = [e for e in read_events(events) if e["kind"] == "round"]
    assert [e["nonfinite_norms"] for e in rounds] == [0, 0]


def test_phase_contract_names():
    # the contract tuple the endpoint/docs key on — order is the span
    # *naming* contract, not execution order (docs/observability.md)
    assert PHASES == ("sample", "local_update", "compress", "aggregate",
                      "server_opt")


# --- event stream ----------------------------------------------------------

def test_eventlog_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("run_start", scenario="x", rounds=2)
    log.emit("round", round=0, loss=1.5)
    log.emit("gap", round=0, gap_ratio=0.25)
    log.emit("run_end", rounds=2)
    log.close()
    events = read_events(path)
    assert [e["kind"] for e in events] == ["run_start", "round", "gap",
                                           "run_end"]
    assert all(e["schema"] == OBS_SCHEMA for e in events)
    assert all(isinstance(e["ts"], float) for e in events)
    assert events[2]["gap_ratio"] == 0.25


# --- gap statistics --------------------------------------------------------

def test_gap_stats_and_ratio():
    s_hat = jnp.asarray([1.0, 2.0, 3.0])
    s = jnp.asarray([1.0, 0.0, 3.0])
    gs = flat_gap_stats(s_hat, s)
    assert float(gs.gap_sq) == 4.0                       # (2-0)^2
    assert float(gs.full_sq) == 10.0                     # 1+0+9
    tree = tree_gap_stats({"a": s_hat, "b": s}, {"a": s, "b": s})
    assert float(tree.gap_sq) == 4.0
    assert float(tree.full_sq) == 20.0
    assert gap_ratio(4.0, 10.0) == pytest.approx(0.4)
    assert gap_ratio(1.0, 0.0) == 0.0                    # guarded division


# --- config + logger -------------------------------------------------------

def test_obs_config_validation():
    assert not ObsConfig().enabled
    assert ObsConfig(diag_every=2).enabled
    assert ObsConfig(metrics_port=0).enabled
    with pytest.raises(ValueError, match="diag_every"):
        ObsConfig(diag_every=-1)
    with pytest.raises(ValueError, match="trace_rounds"):
        ObsConfig(trace_rounds=0)
    with pytest.raises(ValueError, match="metrics_port"):
        ObsConfig(metrics_port=70000)


def test_get_logger_idempotent(capsys):
    a = get_logger("obs-test")
    b = get_logger("obs-test")
    assert a is b and len(a.handlers) == 1
    a.info("hello %d", 7)
    assert "[obs-test] hello 7" in capsys.readouterr().out


# --- metrics endpoint ------------------------------------------------------

def test_metrics_server_scrape():
    server = MetricsServer(port=0).start()
    try:
        snap = {
            "run": {"scenario": "demo", "mode": "host"},
            "round": 3, "rounds_total": 4, "loss": 0.5,
            "phase_seconds": {p: 0.01 for p in PHASES},
            "gap": {"round": 2, "gap_sq": 1.0, "full_sq": 4.0,
                    "gap_ratio": 0.25},
        }
        server.update(snap)
        with urllib.request.urlopen(f"{server.url}/") as r:
            doc = json.loads(r.read())
        assert doc["round"] == 3 and doc["gap"]["gap_ratio"] == 0.25
        with urllib.request.urlopen(f"{server.url}/metrics") as r:
            body = r.read().decode()
        assert "repro_rounds_total 4" in body
        assert "repro_gap_ratio 0.25" in body
        for p in PHASES:
            assert f'repro_phase_seconds{{phase="{p}"}}' in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{server.url}/nope")
        # the renderer alone matches what the endpoint served
        assert render_prometheus(snap) == body
    finally:
        server.stop()


# --- the Eq. 2 gap estimator through the driver ---------------------------

@pytest.mark.parametrize("mode", ["host", "prefetch", "scan"])
def test_gap_zero_at_full_participation(mode):
    """Paper Eq. 2 at q_i = 1: the unbiased estimator IS the full aggregate,
    so the realized gap must be exactly 0.0 — not merely small — in every
    driver mode (vmap and scan engines share the guarantee)."""
    _, led = run_scenario("femnist1-fedavg-full", reduced=True, mode=mode,
                          rounds=4, rounds_per_scan=2,
                          obs=ObsConfig(diag_every=1))
    validate_ledger(led.to_json())
    assert led.gap_rounds == [0, 1, 2, 3]
    assert led.gap_sq == [0.0] * 4
    assert led.gap_ratio == [0.0] * 4
    assert all(fs > 0.0 for fs in led.gap_full_sq)


def test_gap_finite_for_partial_sampling():
    """aocs/uniform cells have a real gap: finite, positive full norm, on
    the diag_every grid, schema-valid — and bitwise identical across
    driver modes (same kernels, same cohorts)."""
    led_by_mode = {}
    for mode in ("host", "prefetch", "scan"):
        _, led = run_scenario("femnist1-fedavg-aocs", reduced=True, mode=mode,
                              rounds=5, rounds_per_scan=1,
                              obs=ObsConfig(diag_every=2))
        validate_ledger(led.to_json())
        assert led.gap_rounds == [0, 2, 4]
        assert all(np.isfinite(g) and g >= 0.0 for g in led.gap_sq)
        assert all(fs > 0.0 for fs in led.gap_full_sq)
        led_by_mode[mode] = led
    for mode in ("prefetch", "scan"):
        assert led_by_mode[mode].gap_ratio == led_by_mode["host"].gap_ratio, mode


def test_gap_rejected_on_mesh():
    """The estimator needs the single-device round (docs/observability.md);
    a sharded cell with diag_every on fails loudly, not wrongly."""
    with pytest.raises(ValueError, match="gap estimator"):
        run_scenario("femnist1-fedavg-aocs-shard-randk", reduced=True,
                     mode="prefetch", rounds=2, obs=ObsConfig(diag_every=1))


# --- zero-interference gate ------------------------------------------------

def test_telemetry_off_ledger_identity(tmp_path):
    """Telemetry on (every sink except ``phases``) vs off: the ledger is
    byte-identical minus wall clock and the gap series.  This is the
    subsystem's acceptance gate — observability must not perturb the run."""
    name = "femnist1-fedavg-aocs-straggler"
    docs = {}
    for tag, obs in (
        ("off", None),
        ("inert", ObsConfig()),          # default config == no telemetry
        ("on", ObsConfig(diag_every=2, metrics_port=0,
                         jsonl=str(tmp_path / "ev.jsonl"))),
    ):
        _, led = run_scenario(name, reduced=True, mode="prefetch", rounds=4,
                              seed=11, obs=obs)
        docs[tag] = json.dumps(_strip_obs(led.to_json(include_masks=True)),
                               sort_keys=True)
    assert docs["inert"] == docs["off"]
    assert docs["on"] == docs["off"]
    # and the event stream actually wrote: rounds + gaps + run_end
    kinds = [e["kind"] for e in read_events(str(tmp_path / "ev.jsonl"))]
    assert kinds.count("round") == 4 and kinds.count("gap") == 2
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"


def test_phased_step_mask_parity(small_ds):
    """The phased executor (5 separate jits) draws bitwise-identical masks
    to the fused step and float-close params/losses (fusion domains differ,
    so params are not bit-exact — why ``ObsConfig.phases`` defaults off)."""
    init, loss, _ = mlp_classifier(small_ds.input_dim, small_ds.num_classes,
                                   hidden=8)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=1,
                  lr_local=0.1, compression="randk", compression_param=0.5)
    engine = RoundEngine(loss, fl)
    fused = jax.jit(engine.make_step())
    phased = make_phased_step(engine)
    key = jax.random.PRNGKey(0)
    params = init(jax.random.fold_in(key, 1))
    w = client_weights(fl)
    rng = np.random.default_rng(0)
    clients = np.arange(fl.n_clients)
    batch = small_ds.sample_round_batches(rng, clients, 1, 4)
    batch = jax.tree_util.tree_map(jnp.asarray, batch)
    k_round = jax.random.fold_in(key, 100)
    p_f, _, m_f = fused(params, None, batch, w, k_round)
    p_p, _, m_p = phased(params, None, batch, w, k_round)
    assert np.array_equal(np.asarray(m_f.mask), np.asarray(m_p.mask))
    assert np.allclose(np.asarray(m_f.loss), np.asarray(m_p.loss), atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p_f),
                    jax.tree_util.tree_leaves(p_p)):
        assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # diag through the phased path agrees with the fused diag step
    fused_diag = jax.jit(engine.make_step(diag=True))
    _, _, md_f = fused_diag(params, None, batch, w, k_round)
    _, _, md_p = phased(params, None, batch, w, k_round, diag=True)
    assert np.allclose(float(md_f.gap.gap_sq), float(md_p.gap.gap_sq),
                       rtol=1e-5)


# --- schema-3 ledger contract ---------------------------------------------

def test_validate_ledger_gap_rejections():
    _, led = run_scenario("femnist1-fedavg-aocs", reduced=True,
                          mode="prefetch", rounds=3,
                          obs=ObsConfig(diag_every=2))
    doc = led.to_json()
    validate_ledger(doc)
    assert doc["schema"] == 3
    bad = json.loads(json.dumps(doc))
    bad["metrics"]["gap_sq"] = bad["metrics"]["gap_sq"][:-1]
    with pytest.raises(ValueError, match="ragged gap"):
        validate_ledger(bad)
    bad = json.loads(json.dumps(doc))
    bad["metrics"]["gap_ratio"] = [-1.0] * len(bad["metrics"]["gap_ratio"])
    with pytest.raises(ValueError, match="negative values in gap"):
        validate_ledger(bad)
    bad = json.loads(json.dumps(doc))
    del bad["metrics"]["wall_ms"]
    with pytest.raises(ValueError, match="wall_ms"):
        validate_ledger(bad)
    bad = json.loads(json.dumps(doc))
    bad["metrics"]["wall_ms"][0] = -1.0
    with pytest.raises(ValueError, match="wall_ms"):
        validate_ledger(bad)


# --- end-to-end endpoint scrape (the CI obs-smoke shape) ------------------

def test_live_endpoint_during_run(tmp_path):
    """Caller-owned Telemetry: run a phased host-mode cell with the gap
    estimator on, then scrape the still-live endpoint — per-phase timings,
    gap ratio and round counters all present (the CI obs-smoke check)."""
    tel = Telemetry(ObsConfig(metrics_port=0, diag_every=2, phases=True,
                              jsonl=str(tmp_path / "ev.jsonl")))
    try:
        _, led = run_scenario("femnist1-fedavg-aocs", reduced=True,
                              mode="host", rounds=4, obs=tel)
        with urllib.request.urlopen(f"{tel.url}/metrics") as r:
            body = r.read().decode()
        assert "repro_rounds_total 4" in body
        assert "repro_gap_ratio" in body
        for p in PHASES:
            assert f'repro_phase_seconds{{phase="{p}"}}' in body
        with urllib.request.urlopen(f"{tel.url}/") as r:
            snap = json.loads(r.read())
        assert snap["rounds_total"] == 4
        assert set(PHASES) <= set(snap["phase_seconds"])
        assert led.gap_rounds == [0, 2]
    finally:
        tel.close()
