"""Multi-round simulation driver: the paper's Sec. 4 evaluation loop as a
subsystem, with a structured metrics ledger and versioned JSON artifacts.

``run_simulation`` replaces the trainer's inner loop with ONE loop over
blocks of rounds, in three execution modes over the same round semantics.
A mode supplies only how a block is drawn and how it is run:

* ``'host'``     — the legacy baseline, one-round blocks: numpy batch
  assembly + upload every round, synchronous with the jitted step (kept as
  the benchmark reference);
* ``'prefetch'`` — the :class:`repro.sim.pool.ClientPool` pipeline,
  one-round blocks drawn one ahead: round k+1's cohort plan is drawn and
  its device gather dispatched while round k's jitted step is still
  running (double-buffered), and the loop never blocks on device results
  until the end;
* ``'scan'``     — scan-over-rounds for fully device-resident pools: blocks
  of up to ``rounds_per_scan`` rounds run inside one jitted ``lax.scan``
  (cohort gather in the scan body), removing per-round dispatch entirely.

What follows a block is written once for every mode: the sampler-state
hand-over, the ledger entry, eval, the first-sync mark, ``wall_ms``, the
telemetry rows and the checkpoint.  A block ends on the first
``eval_every`` or checkpoint round it reaches, so the ledger's
``acc_rounds`` are identical across all three modes (regression-gated in
tests/test_sim.py).

A ``mesh`` argument switches ``'host'`` and ``'prefetch'`` onto the
explicit-collective shard_map round (``fl.engine.make_engine(mesh=...)``):
the prefetch pool goes sharded (``ClientPool(dataset, mesh=...)`` — buffers
``NamedSharding``-placed over ``FLConfig.client_axis``, shard-local cohort
gathers), and the round step shards clients over the same axis, compression
and availability included.  ``'scan'`` mode is single-device only (the
shard_map step inside ``lax.scan`` is not supported — rejected with an
error, see docs/architecture.md#limits).

All three modes consume the host RNG and the JAX round keys in exactly the
legacy trainer's order, so for a fixed seed every mode — and the legacy loop
itself — produces **bitwise-identical per-round participation masks** (the
parity gate in tests/test_sim.py; the batches match bitwise because
``plan_cohort`` replays ``sample_round_batches``'s RNG stream).

A ``system`` argument (:class:`repro.sim.pool.SystemConfig`) switches on the
client-state layer: a device-resident :class:`repro.sim.pool.ClientState`
(Markov availability chains + latency scales over the whole dataset pool) is
stepped once per round — in a one-round block's draw as its own jitted
step, in scan mode inside the ``lax.scan`` carry next to ``(params,
opt_state)`` — and the resulting per-cohort ``AvailabilityTrace`` rides into the round
step, where ``ocs.sampling_plan`` rescales by each client's realized
inclusion probability.  The state key stream is a disjoint fold of the same
round keys, so masks stay bitwise identical across all three modes (and the
mesh) for a fixed seed, and runs WITHOUT a system config are bit-for-bit
what they were before the layer existed.

Every run fills a :class:`SimLedger` — per-round loss / alpha / gamma / sent
/ expected clients, the system-layer counters (selected-before-attrition
``over_selected``, ``deadline_misses``, ``dropouts`` — all zero without a
``system``), per-round ``wall_ms`` on the monotonic clock, plus cumulative
**uplink and downlink** bits (``fl.round.round_bits_duplex``; downlink is
reported separately because the paper's x-axis excludes broadcast,
footnote 5) — serialised as a schema-3 JSON artifact (``validate_ledger`` is
the contract both the tests and the ``bench_sim --smoke`` CI gate assert;
schema 1 lacked the system-layer series, schema 2 lacked ``wall_ms`` and the
gap series).  The round loop never reads the ledger's values back: after
the last round (and at each checkpoint, for the rounds since the previous
one) :func:`fetch_ledger` takes them to the host in one transfer.

An ``obs`` argument (:class:`repro.obs.ObsConfig`, or a live
:class:`repro.obs.Telemetry` when the caller wants the endpoint to outlive
the run) switches on the observability layer: phase/round spans, the online
Eq. 2 gap estimator (``make_step(diag=True)`` every ``diag_every`` rounds —
the sparse ``gap_*`` ledger series and the endpoint's ``repro_gap_ratio``),
the JSONL event stream and the live metrics endpoint.  Telemetry changes NO
round mathematics — masks, norms and params are bitwise what they are with
``obs=None`` (gated in tests/test_obs.py) — but it does change *scheduling*:
prefetch mode gains a per-round device sync so wall times are honest
(the observer effect; docs/observability.md).  The gap estimator is
single-device only (rejected with a mesh); ``ObsConfig.phases`` applies to
host-mode vmap engines and is ignored elsewhere (scan rounds are timed at
block granularity).  With or without telemetry, the call names its host
work (set-up, pool, cohort plans, dispatch, compile, ledger) with
``repro.obs/`` profiler annotations that never sync
(docs/observability.md, "Host spans").

A ``checkpoint`` argument (:class:`repro.checkpoint.CheckpointConfig`, or a
bare directory path) writes a full-fidelity
:class:`repro.checkpoint.RoundCheckpoint` after every ``every``-th round —
params, server-opt state, the pool generator's exact bit-state, the
``ClientState`` chains, the ``SamplerState`` carry, the round index, the
ledger tail and a config fingerprint — atomically, from all three modes,
at block ends (which land on the checkpoint grid as on the eval grid),
holding the RNG and chain state from just before the next block's draw.
``resume=``
restores one and continues at the saved round: the finished run's params
are **bitwise identical** and its ledger JSON **byte-identical** (minus the
wall-clock fields) to the uninterrupted run's, in every mode, with or
without a stateful sampler / Markov client-state — the parity gate in
tests/test_resume.py and the ``resume-smoke`` CI job
(docs/architecture.md#checkpoint--resume).
"""

from __future__ import annotations

import copy
import dataclasses
import json
import os
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.resume import (
    CheckpointConfig,
    RoundCheckpoint,
    load_round,
    run_config_doc,
    save_round,
)
from repro.core.sampling import init_sampler_state, is_stateful
from repro.fl.engine import RoundEngine, make_engine
from repro.fl.round import client_weights, round_bits_duplex
from repro.obs.gap import gap_ratio as _obs_gap_ratio
from repro.obs.telemetry import as_telemetry
from repro.obs.trace import span as obs_span
from repro.sim.pool import (
    ClientPool,
    gather_batch,
    init_client_state,
    stack_plans,
    step_client_state,
)
from repro.sim.scenarios import get_scenario


def build_client_mesh(fl, devices: int | None = None):
    """A 1-D client mesh over the largest feasible local device count.

    The axis (named ``fl.client_axis``) spans the most devices that still
    divide ``fl.n_clients`` — always at least 1, so a single-device container
    exercises the same shard_map code path the production mesh runs.  Shared
    by ``run_scenario`` (``Scenario.sharded`` cells), ``launch/train.py
    --shard`` and ``benchmarks/bench_sim.py``.
    """
    n_dev = jax.device_count() if devices is None else devices
    shards = max(d for d in range(1, n_dev + 1) if fl.n_clients % d == 0)
    return jax.make_mesh((shards,), (fl.client_axis,))

SIM_SCHEMA = 3
MODES = ("host", "prefetch", "scan")

# per-round series every schema-3 ledger must carry, all the same length
# (schema 1 lacked the three system-layer counters; schema 2 lacked wall_ms)
LEDGER_SERIES = (
    "loss", "alpha", "gamma", "sent", "expected_clients",
    "over_selected", "deadline_misses", "dropouts",
    "uplink_bits", "downlink_bits", "wall_ms",
)

# sparse per-diagnostic-round series (schema 3; empty when the run had no
# obs gap estimator) — all four the same length, indexed by gap_rounds
GAP_SERIES = ("gap_rounds", "gap_sq", "gap_full_sq", "gap_ratio")

# the RoundMetrics fields the ledger reads back from the device
LEDGER_FIELDS = (
    "loss", "alpha", "gamma", "sent_clients", "expected_clients",
    "selected_clients", "deadline_misses", "dropouts", "mask", "norms",
)

# per-round RoundMetrics are stacked on the device this many rounds at a
# time before the ledger reads them (the last chunk padded to it), so every
# call of a process shares one compile of the stacking program
LEDGER_CHUNK = 64


@jax.jit
def _stack_rounds(rows):
    """``LEDGER_CHUNK`` rounds' ledger fields, each stacked along axis 0."""
    return tuple(jnp.stack(col) for col in zip(*rows))


def fetch_ledger(entries, extra=()):
    """The ``LEDGER_FIELDS`` of ``entries`` on the host, plus ``extra``.

    ``entries`` are RoundMetrics of one round each, or of a block of rounds
    stacked along axis 0 (scan mode); the result holds one host array per
    field, every entry's rounds stacked along axis 0 in order.  A device
    read costs about the same whatever its size, so one-round entries are
    first stacked on the device ``LEDGER_CHUNK`` at a time (the last chunk
    padded with its final entry, trimmed here), and everything — chunks,
    blocks and ``extra`` — goes to the host in ONE ``jax.device_get``,
    which starts every copy before it waits on any.
    """
    parts, run = [], []   # (fields, rounds) per device copy; pending rounds

    def flush():
        for i in range(0, len(run), LEDGER_CHUNK):
            part = run[i:i + LEDGER_CHUNK]
            pad = [part[-1]] * (LEDGER_CHUNK - len(part))
            parts.append((_stack_rounds(tuple(part + pad)), len(part)))
        run.clear()

    for m in entries:
        fields = tuple(getattr(m, name) for name in LEDGER_FIELDS)
        if np.ndim(m.loss) == 0:
            run.append(fields)
        else:
            flush()
            parts.append((fields, len(m.loss)))
    flush()
    host, extra = jax.device_get(([f for f, _ in parts], list(extra)))
    cols = [np.concatenate([h[j][:n] for h, (_, n) in zip(host, parts)], 0)
            for j in range(len(LEDGER_FIELDS))]
    return cols, extra


@dataclass
class SimLedger:
    """Structured metrics ledger of one simulation run (artifact schema 3).

    Per-round series (``LEDGER_SERIES``, including the system-layer counters
    ``over_selected``/``deadline_misses``/``dropouts`` — zeros when the run
    had no :class:`~repro.sim.pool.SystemConfig` — and per-round ``wall_ms``
    on the monotonic clock: honest per-round syncs in host mode, dispatch
    cadence in prefetch, block-amortized in scan), the sparse gap series
    (``GAP_SERIES`` — the obs layer's Eq. 2 estimator on the ``diag_every``
    grid, empty without it), the eval curve (``acc_rounds``/``acc``,
    rectangular — no ``(round, value)`` tuples) and the run's throughput.
    ``masks``/``norms`` are kept in memory for parity tests and are written
    to JSON only on request (``include_masks``).
    """

    mode: str
    scenario: str | None = None
    fl: dict = field(default_factory=dict)
    workload: dict = field(default_factory=dict)
    loss: list = field(default_factory=list)
    alpha: list = field(default_factory=list)
    gamma: list = field(default_factory=list)
    sent: list = field(default_factory=list)
    expected_clients: list = field(default_factory=list)
    over_selected: list = field(default_factory=list)    # pre-attrition draws
    deadline_misses: list = field(default_factory=list)
    dropouts: list = field(default_factory=list)
    uplink_bits: list = field(default_factory=list)      # cumulative
    downlink_bits: list = field(default_factory=list)    # cumulative
    wall_ms: list = field(default_factory=list)          # per-round, monotonic clock
    gap_rounds: list = field(default_factory=list)       # diag_every grid
    gap_sq: list = field(default_factory=list)           # ‖ŝ − s‖² per diag round
    gap_full_sq: list = field(default_factory=list)      # ‖s‖² per diag round
    gap_ratio: list = field(default_factory=list)        # gap_sq / full_sq
    acc_rounds: list = field(default_factory=list)
    acc: list = field(default_factory=list)
    masks: list = field(default_factory=list)            # (n,) bool per round
    norms: list = field(default_factory=list)            # (n,) f32 per round
    wall_s: float = 0.0
    rounds_per_sec: float = 0.0                          # steady-state (post-compile)

    def to_json(self, include_masks: bool = False) -> dict:
        """The schema-3 artifact document (see :func:`validate_ledger`)."""
        doc = {
            "schema": SIM_SCHEMA,
            "scenario": self.scenario,
            "mode": self.mode,
            "fl": self.fl,
            "workload": self.workload,
            "metrics": {name: getattr(self, name) for name in
                        LEDGER_SERIES + GAP_SERIES + ("acc_rounds", "acc")},
            "wall_s": self.wall_s,
            "rounds_per_sec": self.rounds_per_sec,
        }
        if include_masks:
            doc["masks"] = [np.asarray(m).astype(int).tolist() for m in self.masks]
        return doc

    def write(self, path: str, include_masks: bool = False) -> str:
        """Serialise the ledger as a JSON artifact; returns the path."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(include_masks=include_masks), f, indent=1)
        return path


def validate_ledger(doc: dict) -> None:
    """Assert the schema-3 ledger contract; raises ``ValueError`` on breach.

    The single source of truth for what a sim artifact must contain — the
    scenario-grid smoke test and the ``bench_sim --smoke`` CI step both call
    this, so the schema cannot drift silently.  Schema 2 added the per-round
    system-layer counters (``over_selected``, ``deadline_misses``,
    ``dropouts``), length-checked with every other series and required to be
    non-negative; schema 3 adds per-round ``wall_ms`` (finite, non-negative,
    monotonic-clock measured) and the sparse obs gap series (``GAP_SERIES``
    — rectangular across the four, finite, non-negative, empty when the run
    had no gap estimator).
    """
    if doc.get("schema") != SIM_SCHEMA:
        raise ValueError(f"ledger schema {doc.get('schema')!r} != {SIM_SCHEMA}")
    if doc.get("mode") not in MODES:
        raise ValueError(f"ledger mode {doc.get('mode')!r} not in {MODES}")
    for block in ("fl", "workload", "metrics"):
        if not isinstance(doc.get(block), dict):
            raise ValueError(f"ledger is missing the {block!r} block")
    metrics = doc["metrics"]
    n = None
    for series in LEDGER_SERIES:
        vals = metrics.get(series)
        if not isinstance(vals, list):
            raise ValueError(f"ledger metrics lack the {series!r} series")
        if n is None:
            n = len(vals)
        if len(vals) != n:
            raise ValueError(
                f"ragged ledger: {series!r} has {len(vals)} entries, want {n}"
            )
    if not n:
        raise ValueError("ledger records zero rounds")
    for series in ("loss", "alpha", "gamma", "wall_ms"):
        if not np.all(np.isfinite(np.asarray(metrics[series], np.float64))):
            raise ValueError(f"non-finite values in ledger series {series!r}")
    if np.any(np.asarray(metrics["wall_ms"], np.float64) < 0):
        raise ValueError("negative wall_ms in ledger")
    for series in ("acc_rounds", "acc"):
        if not isinstance(metrics.get(series), list):
            raise ValueError(f"ledger metrics lack the {series!r} series")
    if len(metrics["acc_rounds"]) != len(metrics["acc"]):
        raise ValueError("acc_rounds and acc series lengths differ")
    m_gap = None
    for series in GAP_SERIES:
        vals = metrics.get(series)
        if not isinstance(vals, list):
            raise ValueError(f"ledger metrics lack the {series!r} series")
        if m_gap is None:
            m_gap = len(vals)
        if len(vals) != m_gap:
            raise ValueError(
                f"ragged gap series: {series!r} has {len(vals)}, want {m_gap}"
            )
        arr = np.asarray(vals, np.float64)
        if not np.all(np.isfinite(arr)):
            raise ValueError(f"non-finite values in gap series {series!r}")
        if np.any(arr < 0):
            raise ValueError(f"negative values in gap series {series!r}")
    for series in ("over_selected", "deadline_misses", "dropouts"):
        if np.any(np.asarray(metrics[series], np.int64) < 0):
            raise ValueError(f"negative counts in ledger series {series!r}")
    for series in ("uplink_bits", "downlink_bits"):
        if np.any(np.diff(np.asarray(metrics[series], np.int64)) < 0):
            raise ValueError(f"cumulative series {series!r} decreases")
    if "rounds_per_sec" not in doc or "wall_s" not in doc:
        raise ValueError("ledger lacks throughput fields")


def run_simulation(
    dataset,
    init_fn,
    loss_fn,
    fl,
    rounds: int,
    *,
    batch_size: int = 20,
    mode: str = "prefetch",
    rounds_per_scan: int = 8,
    eval_fn=None,
    eval_batch=None,
    eval_every: int = 5,
    seed: int = 0,
    local_epoch: bool = True,
    server_opt=None,
    mesh=None,
    system=None,
    scenario_name: str | None = None,
    artifact: str | None = None,
    obs=None,
    checkpoint=None,
    resume=None,
) -> tuple:
    """Run ``rounds`` communication rounds; returns ``(params, SimLedger)``.

    One block loop, three execution modes (module docstring); all modes draw the
    cohort (``rng.choice`` without replacement), the per-client example
    permutations and the per-round keys (``fold_in(key, 1000 + k)``) in the
    legacy trainer's exact order, so the per-round participation masks are
    **bitwise** identical across modes and to the legacy loop for the same
    seed — with or without a ``mesh`` (the shard_map round shares the
    engines' sampling math and compression subkeys).  ``fl.weights ==
    'data_size'`` takes each cohort's slice of ``dataset.sizes()``
    (normalized per round) — the legacy loop silently dropped it.
    ``system`` (a :class:`~repro.sim.pool.SystemConfig`) switches on the
    client-state layer (module docstring): mutually exclusive with the
    scalar ``fl.availability < 1`` path, since the trace generalizes it.
    ``artifact`` (a path) serialises the ledger on completion.  ``obs``
    (an :class:`~repro.obs.ObsConfig`, or a live
    :class:`~repro.obs.Telemetry` whose lifecycle the caller keeps) switches
    on the observability layer — module docstring and docs/observability.md;
    the gap estimator needs a single-device run (``diag_every`` with a
    ``mesh`` is rejected: the shard_map round has no diag variant).
    ``checkpoint`` (a :class:`~repro.checkpoint.CheckpointConfig` or a bare
    directory path) writes a full-fidelity
    :class:`~repro.checkpoint.RoundCheckpoint` after every ``every``-th
    round and after the last; ``resume`` (a checkpoint root or a specific
    ``step-XXXXXXXX`` directory) restores one — rejecting it with a
    ``ValueError`` when its config fingerprint differs from this run's —
    and continues at the saved round, reproducing the uninterrupted run's
    params bitwise and its ledger byte-for-byte minus the wall-clock fields
    (module docstring; docs/architecture.md#checkpoint--resume).
    """
    if mode not in MODES:
        raise ValueError(f"unknown sim mode {mode!r}; want one of {MODES}")
    tel, tel_owned = as_telemetry(obs)
    diag_on = tel is not None and tel.cfg.diag_every > 0
    if diag_on and mesh is not None:
        raise ValueError(
            "the obs gap estimator (ObsConfig.diag_every > 0) does not "
            "support a mesh: the shard_map round has no diag variant — run "
            "single-device, or drop diag_every (docs/architecture.md#limits)"
        )
    if system is not None and fl.availability < 1.0:
        raise ValueError(
            "system config and scalar fl.availability < 1 are mutually "
            "exclusive: the availability trace generalizes Appendix E's "
            "Bernoulli(q) — encode q as SystemConfig(p_up=q, p_down=1-q)"
        )
    if fl.n_clients > dataset.n_clients:
        raise ValueError(
            f"FLConfig.n_clients={fl.n_clients} exceeds the dataset's client "
            f"pool of {dataset.n_clients} clients: each round draws the cohort "
            f"without replacement, so n_clients must be <= the pool size "
            f"(shrink FLConfig.n_clients or enlarge the dataset)"
        )
    if mode == "scan" and rounds_per_scan < 1:
        raise ValueError(f"rounds_per_scan must be >= 1, got {rounds_per_scan}")
    if mode == "scan" and mesh is not None:
        raise ValueError(
            "sim mode 'scan' does not support a mesh: the shard_map round "
            "cannot run inside the scan-over-rounds block — use mode='host' "
            "or mode='prefetch' with the mesh, or drop the mesh to keep "
            "scan-over-rounds (docs/architecture.md#limits)"
        )

    # every span is annotated for the profiler; those given `tel` also
    # record into it and wait on their block target, so with telemetry off
    # no span syncs (obs/trace.py).  The call's set-up, before any pool or
    # round: engine, params, client-state and sampler init.
    with obs_span("setup"):
        # mesh-aware engine selection, BEFORE any RNG or device work: with a
        # mesh, host/prefetch run the explicit-collective shard_map round; a
        # rejected config (unknown compressor/backend, server_opt on the mesh)
        # raises here — no key is consumed and no pool is uploaded.
        engine = None
        if mesh is not None:
            round_step_fn = make_engine(loss_fn, fl, server_opt, mesh=mesh)
            step_factory = lambda diag=False: round_step_fn
        else:
            engine = RoundEngine(loss_fn, fl, server_opt)
            step_factory = engine.make_step
        # phased execution (real per-phase spans) applies to host-mode vmap
        # engines only; elsewhere the knob is ignored and rounds are timed as
        # whole "round" spans (scan: one span per block).
        use_phased = (
            tel is not None and tel.cfg.phases and mode == "host"
            and engine is not None and engine.memory == "vmap"
        )

        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(seed)
        params = init_fn(jax.random.fold_in(key, 1))
        dim = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
        opt_state = server_opt.init(params) if server_opt is not None else ()
        # client-state layer: chains over the WHOLE dataset pool, initialised at
        # stationarity from a dedicated fold (the params fold is 1, rounds are
        # 1000+k — fold 2 is untouched on every pre-existing path).
        state = None
        if system is not None:
            state = init_client_state(
                dataset.n_clients, system, jax.random.fold_in(key, 2)
            )
            state_step = jax.jit(
                lambda st, kk, c: step_client_state(st, kk, c, system)
            )
        # stateful samplers (cyclic/threshold): their SamplerState rides through
        # the round loop exactly like the client-state chain — fed into every
        # round_step, read back from metrics.sampler_state (host/prefetch) or
        # carried in the lax.scan carry (scan mode).
        samp = init_sampler_state() if is_stateful(fl.sampler) else None

    sizes = np.asarray(dataset.sizes())
    uniform_w = client_weights(fl)

    def cohort_weights(clients):
        # fl.weights == 'data_size' reaches the engine as the cohort's slice
        # of dataset.sizes(), normalized per round (client_weights).
        if fl.weights == "data_size":
            return client_weights(fl, jnp.asarray(sizes[np.asarray(clients)]))
        return uniform_w

    def draw_cohort():
        return rng.choice(dataset.n_clients, size=fl.n_clients, replace=False)

    def want_eval(k):
        return eval_fn is not None and (k % eval_every == 0 or k == rounds - 1)

    dev_metrics = []          # device-side RoundMetrics not read yet (blocks in scan)
    dev_evals = []            # (round, device scalar; on the host once read)
    wall_ms = []              # per-round wall (monotonic clock; THIS process)
    gap_records = []          # (round, gap_sq, full_sq) on the diag_every grid
    tel_up = tel_down = tel_miss = tel_drop = 0   # live endpoint counters
    t_first, first_units = None, 0

    # ---- checkpoint / resume: full-fidelity RoundCheckpoints ----
    ck = None
    if checkpoint is not None:
        ck = (checkpoint if isinstance(checkpoint, CheckpointConfig)
              else CheckpointConfig(str(checkpoint)))
    cfg_doc = None
    if ck is not None or resume is not None:
        cfg_doc = run_config_doc(
            fl, seed=seed, batch_size=batch_size, local_epoch=local_epoch,
            pool_clients=int(dataset.n_clients), model_dim=dim, system=system,
            eval_every=int(eval_every) if eval_fn is not None else None,
            scenario=scenario_name,
        )
    k0 = 0
    tail = {name: [] for name in LEDGER_SERIES}
    tail_masks = tail_norms = None
    if resume is not None:
        rc = load_round(
            resume, params=params, opt_state=opt_state, client_state=state,
            sampler_state=samp, config=cfg_doc,
        )
        if rc.round >= rounds:
            raise ValueError(
                f"checkpoint at {resume!r} already covers round {rc.round} "
                f"but the run asks for rounds={rounds} — raise rounds to "
                f"extend the run"
            )
        k0 = rc.round
        params, opt_state = rc.params, rc.opt_state
        if state is not None:
            state = rc.client_state
        if samp is not None:
            samp = rc.sampler_state
        # continue the pool generator mid-stream: every later cohort draw
        # and permutation is the one the uninterrupted run would have made
        rng.bit_generator.state = rc.rng_state
        tail = rc.series
        tail_masks = np.asarray(rc.masks, bool)
        tail_norms = np.asarray(rc.norms, np.float32)
        gap_records.extend(rc.gap_records)
        dev_evals.extend(rc.evals)

    def need_ckpt(k):
        # after round k: on the every-grid, and always after the final round
        return ck is not None and ((k + 1) % ck.every == 0 or k + 1 == rounds)

    host_rows = [[] for _ in LEDGER_FIELDS]   # per field: the rounds read so far

    def splice_series():
        """Full-run per-round series plus (done, n) mask/norm arrays.

        The resumed tail's entries (JSON round-trips python floats exactly)
        are followed by this process's live rounds, converted with the same
        ``float()``/``int()`` calls either way — so a spliced ledger is
        byte-identical to the uninterrupted run's, not merely close.
        """
        if dev_metrics:
            # the rounds not read yet (and every eval) in one device read, so
            # each checkpoint reads only the rounds since the previous one
            with obs_span("ledger_read"):
                cols, evals = fetch_ledger(dev_metrics, [v for _, v in dev_evals])
            for col, new in zip(host_rows, cols):
                col.append(new)
            dev_evals[:] = [(k, v) for (k, _), v in zip(dev_evals, evals)]
            dev_metrics.clear()
        (losses, alphas, gammas, sents, expected, selected, misses, drops,
         masks_l, norms_l) = (np.concatenate(col, 0) for col in host_rows)
        masks_l = masks_l.astype(bool)
        norms_l = norms_l.astype(np.float32)
        ser = {name: list(tail[name]) for name in LEDGER_SERIES}
        up_total = ser["uplink_bits"][-1] if ser["uplink_bits"] else 0
        down_total = ser["downlink_bits"][-1] if ser["downlink_bits"] else 0
        for i in range(masks_l.shape[0]):
            up, down = round_bits_duplex(fl, dim, masks_l[i])
            up_total += int(up)
            down_total += int(down)
            ser["loss"].append(float(losses[i]))
            ser["alpha"].append(float(alphas[i]))
            ser["gamma"].append(float(gammas[i]))
            ser["sent"].append(int(sents[i]))
            ser["expected_clients"].append(float(expected[i]))
            ser["over_selected"].append(int(selected[i]))
            ser["deadline_misses"].append(int(misses[i]))
            ser["dropouts"].append(int(drops[i]))
            ser["uplink_bits"].append(up_total)
            ser["downlink_bits"].append(down_total)
            ser["wall_ms"].append(float(wall_ms[i]))
        if tail_masks is not None:
            return (ser, np.concatenate([tail_masks, masks_l], 0),
                    np.concatenate([tail_norms, norms_l], 0))
        return ser, masks_l, norms_l

    def write_ckpt(k_done, rng_st, cl_state, s_state):
        # k_done = the last completed round; everything device-side is
        # pulled to host (device_get) before the next step can donate it
        ser, m_all, n_all = splice_series()
        save_round(ck, RoundCheckpoint(
            round=k_done + 1,
            params=jax.device_get(params),
            opt_state=jax.device_get(opt_state),
            client_state=(jax.device_get(cl_state)
                          if cl_state is not None else None),
            sampler_state=(jax.device_get(s_state)
                           if s_state is not None else None),
            rng_state=rng_st,
            series=ser,
            gap_records=list(gap_records),
            evals=[(int(k), float(v)) for k, v in dev_evals],
            masks=m_all,
            norms=n_all,
            config=cfg_doc,
        ))

    def tel_round(k, metrics, ms_val):
        # per-round endpoint/event record (telemetry on only), after the
        # round's gap on the diag_every grid.  The mask pull syncs the
        # device — part of the documented observer effect.
        nonlocal tel_up, tel_down, tel_miss, tel_drop
        if diag_on and tel.want_gap(k):
            gs, fs = float(metrics.gap.gap_sq), float(metrics.gap.full_sq)
            gap_records.append((k, gs, fs))
            tel.record_gap(k, gs, fs)
        up, down = round_bits_duplex(fl, dim, np.asarray(metrics.mask))
        tel_up += int(up)
        tel_down += int(down)
        tel_miss += int(metrics.deadline_misses)
        tel_drop += int(metrics.dropouts)
        tel.record_round(
            k, loss=float(metrics.loss), sent_clients=int(metrics.sent_clients),
            wall_ms=ms_val, uplink_bits_total=tel_up,
            downlink_bits_total=tel_down, deadline_misses_total=tel_miss,
            dropouts_total=tel_drop,
        )

    def block_end(k, longest):
        # the last round of the block that starts at round k: at most
        # `longest` rounds, ending early on the first eval or checkpoint
        # round it reaches (eval and checkpoint happen after a round's
        # step, so scan's acc_rounds and checkpoints land on the same
        # rounds as a one-round block's)
        last = min(k + longest, rounds) - 1
        return next((j for j in range(k, last)
                     if want_eval(j) or need_ckpt(j)), last)

    if tel is not None:
        tel.run_start(
            scenario=scenario_name, mode=mode, sampler=fl.sampler,
            n_clients=fl.n_clients, rounds=rounds,
            backend=jax.default_backend(),
        )
    t_start = time.perf_counter()

    # ---- per mode: how a block is drawn (its `data` span) and run ----
    cpool = None
    if mode != "host":
        cpool = ClientPool(dataset, mesh=mesh, client_axis=fl.client_axis)
    if use_phased:
        from repro.obs.phased import make_phased_step

        phased_step = make_phased_step(engine, tel)
    elif mode != "scan":
        # the jitted round step, and its diag twin when the gap estimator
        # is on (the diag step is chosen per round)
        steps = {d: jax.jit(step_factory(d), donate_argnums=(0, 1))
                 for d in {False, diag_on}}
    else:
        # with the gap estimator on, the WHOLE block compiles with the diag
        # step (per-round step selection cannot live inside lax.scan); the
        # ledger still records gaps on the diag_every grid only.
        step_fn = step_factory(diag_on)
        shapes = cpool.example_shapes

        def chunk_fn(buffers, params, opt_state, st, sp, clients_s, take_s,
                     smask_s, w_s, keys_s):
            # the client-state chain and the SamplerState ride in the scan
            # carry next to (params, opt_state); None when the run has none
            def body(carry, xs):
                p, o, s, sp = carry
                c, t, sm, w, kk = xs
                trace = None
                if s is not None:
                    # same step_client_state, same per-round key fold as the
                    # one-round modes' jitted state step — bitwise identical
                    s, trace = step_client_state(s, kk, c, system)
                p, o, m = step_fn(
                    p, o, gather_batch(buffers, shapes, c, t, sm), w, kk, trace, sp
                )
                return (p, o, s, m.sampler_state), m

            (params, opt_state, st, sp), ms = jax.lax.scan(
                body, (params, opt_state, st, sp),
                (clients_s, take_s, smask_s, w_s, keys_s),
            )
            return params, opt_state, st, sp, ms

        chunk = jax.jit(chunk_fn, donate_argnums=(1, 2, 3, 4))

    def step_state(k, clients):
        # round k's key, and its client-state step when the run has a system
        nonlocal state
        kk = jax.random.fold_in(key, 1000 + k)
        trace = None
        if state is not None:
            state, trace = state_step(state, kk, jnp.asarray(clients))
        return kk, trace

    def draw_host(k, n):
        with obs_span("data", tel) as s:
            clients = draw_cohort()
            w = cohort_weights(clients)
            batch = dataset.sample_round_batches(
                rng, clients, fl.local_steps, batch_size, local_epoch
            )
            batch = {bk: jnp.asarray(v) for bk, v in batch.items()}
            s.block(batch)
        return (batch, w, *step_state(k, clients))

    def draw_prefetch(k, n):
        with obs_span("data", tel):
            with obs_span("plan"):
                clients = draw_cohort()
                plan = cpool.plan(rng, clients, fl.local_steps, batch_size, local_epoch)
            kk, trace = step_state(k, plan.clients)
            w = cohort_weights(clients)
            return cpool.gather(plan), w, kk, trace

    def draw_scan(k, n):
        with obs_span("data", tel):
            with obs_span("plan"):
                plans, w_s, keys_s = [], [], []
                for j in range(k, k + n):
                    clients = draw_cohort()
                    plans.append(
                        cpool.plan(rng, clients, fl.local_steps, batch_size, local_epoch)
                    )
                    w_s.append(cohort_weights(clients))
                    keys_s.append(jax.random.fold_in(key, 1000 + j))
                clients_s, take_s, smask_s = stack_plans(plans)
            with obs_span("gather"):
                # the block's index uploads; the gather itself runs in the
                # scan body
                return (jnp.asarray(clients_s), jnp.asarray(take_s),
                        jnp.asarray(smask_s), jnp.stack(w_s), jnp.stack(keys_s))

    compiled = False

    def launch(fn, *args):
        # one `round` span per dispatch; the call's first dispatch traces,
        # lowers and compiles its program (or loads it from the persistent
        # cache) inside a nested `compile` span
        nonlocal compiled
        with obs_span("round", tel) as s:
            if compiled:
                out = fn(*args)
            else:
                compiled = True
                with obs_span("compile"):
                    out = fn(*args)
            s.block(out[-1].loss)
        return out

    def run_round(cur, k):
        # one round's step (the chain already stepped in the draw), the
        # diag twin on the diag_every grid
        batch, w, kk, trace = cur
        diag = diag_on and tel.want_gap(k)
        if use_phased:
            p, o, m = phased_step(params, opt_state, batch, w, kk, trace, samp,
                                  diag=diag)
        else:
            p, o, m = launch(steps[diag], params, opt_state, batch, w, kk, trace,
                             samp)
        return p, o, state, m.sampler_state, m

    def run_block(xs, k):
        return launch(chunk, cpool.buffers, params, opt_state, state, samp, *xs)

    draw = {"host": draw_host, "prefetch": draw_prefetch, "scan": draw_scan}[mode]
    run = run_block if mode == "scan" else run_round
    longest = rounds_per_scan if mode == "scan" else 1
    # prefetch double-buffers: round k+1 is drawn (plan, chain step, gather
    # dispatch) while round k's step still runs
    ahead = mode == "prefetch"
    nxt = draw(k0, 1) if ahead else None
    k = k0
    while k < rounds:
        t_blk = time.perf_counter()
        last = block_end(k, longest)
        n = last - k + 1
        if tel is not None:
            tel.round_start(k)
        # a checkpoint holds the RNG and chain state from just before the
        # next block's draw: prefetch makes that draw now, before this block
        # runs, so it snapshots them; host and scan draw after the
        # checkpoint, so theirs is the live state
        snap = None
        if not ahead:
            cur = draw(k, n)
        elif last + 1 < rounds:
            if need_ckpt(last):
                snap = (copy.deepcopy(rng.bit_generator.state), state)
            cur, nxt = nxt, draw(last + 1, 1)
        else:
            cur = nxt
        params, opt_state, state, samp, ms = run(cur, k)
        dev_metrics.append(ms)
        if want_eval(last):
            dev_evals.append((last, eval_fn(params, eval_batch)))
        if mode == "host":
            # the host loop is synchronous by construction (legacy
            # behaviour): it blocks before assembling the next round's batch
            jax.block_until_ready(ms.loss)
        elif t_first is None:
            # the only telemetry-off mid-run sync: marks the end of the
            # compile round, and waits for all queued before it — the
            # rest of the pool's asynchronous copy to the device too
            with obs_span("first_sync"):
                jax.block_until_ready(ms.loss)
        if t_first is None:
            t_first, first_units = time.perf_counter(), n
        # per-round wall: honest per-round syncs in host mode (and with
        # telemetry on, whose spans sync), dispatch cadence in prefetch,
        # the block's cadence amortised over its rounds in scan
        blk_ms = (time.perf_counter() - t_blk) * 1e3 / n
        wall_ms.extend([blk_ms] * n)
        if tel is not None:
            for i in range(n):   # a scan block's metrics are stacked
                row = ms if np.ndim(ms.loss) == 0 else jax.tree_util.tree_map(lambda x: x[i], ms)
                tel_round(k + i, row, blk_ms)
        if need_ckpt(last):
            rng_st, cl_st = snap or (copy.deepcopy(rng.bit_generator.state), state)
            write_ckpt(last, rng_st, cl_st, samp)
        k = last + 1

    jax.block_until_ready(params)
    if dev_metrics:
        jax.block_until_ready(dev_metrics[-1].loss)
    t_end = time.perf_counter()

    # the ledger: the one device read of the rounds not read yet (nested
    # `ledger_read`) and the host arithmetic over them, after the final
    # sync (annotations only; nothing records them)
    with obs_span("ledger"):
        ledger = SimLedger(
            mode=mode,
            scenario=scenario_name,
            fl=dataclasses.asdict(fl),
            workload={
                "rounds": rounds,
                "batch_size": batch_size,
                "pool_clients": int(dataset.n_clients),
                "model_dim": dim,
                "seed": seed,
                "local_epoch": bool(local_epoch),
                "backend_platform": jax.default_backend(),
                **({"rounds_per_scan": rounds_per_scan} if mode == "scan" else {}),
                **({"pool_bytes": cpool.nbytes} if mode != "host" else {}),
                **(
                    {"mesh_axis_size": int(np.prod(mesh.devices.shape))}
                    if mesh is not None else {}
                ),
                **(
                    {"system": dataclasses.asdict(system)}
                    if system is not None else {}
                ),
            },
        )
        # the resumed tail (if any) splices ahead of this process's live rounds
        # with identical scalar conversions — byte-identical artifact either way
        ser, masks_all, norms_all = splice_series()
        for name in LEDGER_SERIES:
            setattr(ledger, name, ser[name])
        ledger.masks = list(masks_all)
        ledger.norms = list(norms_all)
        for k, gs, fs in gap_records:
            ledger.gap_rounds.append(int(k))
            ledger.gap_sq.append(gs)
            ledger.gap_full_sq.append(fs)
            ledger.gap_ratio.append(_obs_gap_ratio(gs, fs))
        for k, v in dev_evals:
            ledger.acc_rounds.append(int(k))
            ledger.acc.append(float(v))
    ledger.wall_s = t_end - t_start
    # throughput counts the rounds THIS process ran, not the resumed tail
    steady = (rounds - k0) - first_units
    if t_first is not None and steady > 0 and t_end > t_first:
        ledger.rounds_per_sec = steady / (t_end - t_first)
    else:
        ledger.rounds_per_sec = (rounds - k0) / max(t_end - t_start, 1e-9)
    if tel is not None:
        tel.finish(rounds=rounds, wall_s=ledger.wall_s,
                   rounds_per_sec=ledger.rounds_per_sec)
        if tel_owned:
            tel.close()
    if artifact:
        ledger.write(artifact)
    return params, ledger


def run_scenario(
    scenario,
    *,
    reduced: bool = False,
    mode: str = "prefetch",
    rounds: int | None = None,
    rounds_per_scan: int = 8,
    seed: int | None = None,
    mesh=None,
    artifact: str | None = None,
    obs=None,
    checkpoint=None,
    resume=None,
) -> tuple:
    """Run a registered scenario (by name or instance) end to end.

    Builds the scenario's dataset and model (``reduced=True`` shrinks both —
    the scenario-grid smoke path), then delegates to :func:`run_simulation`.
    ``Scenario.sharded`` cells (and an explicit ``mesh``) run the shard_map
    round with the sharded client pool — when the cell is sharded and no mesh
    is passed, :func:`build_client_mesh` spans the local devices.
    ``Scenario.system`` cells thread their
    :class:`~repro.sim.pool.SystemConfig` into the client-state layer.
    ``obs`` threads an :class:`~repro.obs.ObsConfig`/
    :class:`~repro.obs.Telemetry` into the observability layer;
    ``checkpoint``/``resume`` thread the full-fidelity round-checkpoint
    layer (:func:`run_simulation`) — the scenario's own name rides in the
    config fingerprint, so a checkpoint from one scenario refuses to resume
    another.  Returns ``(params, SimLedger)``.
    """
    sc = get_scenario(scenario) if isinstance(scenario, str) else scenario
    if reduced:
        sc = sc.reduced()
    if mesh is None and sc.sharded:
        mesh = build_client_mesh(sc.fl)
    if mesh is not None and mode == "scan":
        raise ValueError(
            f"scenario {sc.name!r} runs on a mesh, which sim mode 'scan' "
            "does not support — use mode 'host' or 'prefetch' "
            "(docs/architecture.md#limits)"
        )
    ds = sc.build_dataset(reduced=reduced)
    init_fn, loss_fn, _ = sc.build_model(ds)
    return run_simulation(
        ds, init_fn, loss_fn, sc.fl, rounds if rounds is not None else sc.rounds,
        batch_size=sc.batch_size, mode=mode, rounds_per_scan=rounds_per_scan,
        seed=sc.seed if seed is None else seed, mesh=mesh, system=sc.system,
        scenario_name=sc.name, artifact=artifact, obs=obs,
        checkpoint=checkpoint, resume=resume,
    )
