"""repro.sim subsystem: pool-gather bitwise parity with host batch assembly,
driver-vs-legacy-loop mask parity across all execution modes (the acceptance
gate of the trainer refactor), cohort-size validation, the data_size weights
regression, the scenario-grid smoke, the schema-3 ledger contract, and the
client-state layer's determinism regression (same seed => byte-identical
straggler-cell ledger JSON in all three driver modes)."""

import contextlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.data import FederatedDataset, femnist_like
from repro.fl.engine import RoundEngine
from repro.fl.round import client_weights
from repro.fl.trainer import run_training
from repro.models.simple import mlp_classifier
from repro.sim import driver as drv
from repro.sim import pool as pool_mod
from repro.sim import (
    ClientPool,
    build_client_mesh,
    get_scenario,
    list_scenarios,
    run_scenario,
    run_simulation,
    validate_ledger,
)
from repro.sim.driver import LEDGER_CHUNK
from repro.sim.pool import device_shape

MODES = ("host", "prefetch", "scan")


@pytest.fixture(scope="module")
def small_ds():
    return femnist_like(
        dataset_id=1, n_clients=24, dim=48, num_classes=10, base_examples=24, seed=0
    )


def _model(ds, hidden=16):
    return mlp_classifier(ds.input_dim, ds.num_classes, hidden=hidden)


def _legacy_loop(ds, init, loss, fl, rounds, batch_size, seed):
    """Byte-for-byte the pre-sim run_training inner loop (uniform weights)."""
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    params = init(jax.random.fold_in(key, 1))
    step = jax.jit(RoundEngine(loss, fl, None).make_step(), donate_argnums=(0, 1))
    w = client_weights(fl)
    masks = []
    for k in range(rounds):
        clients = rng.choice(ds.n_clients, size=fl.n_clients, replace=False)
        batch = ds.sample_round_batches(rng, clients, fl.local_steps, batch_size)
        batch = {k_: jnp.asarray(v) for k_, v in batch.items()}
        params, _, m = step(params, (), batch, w, jax.random.fold_in(key, 1000 + k))
        masks.append(np.asarray(m.mask))
    return params, masks


def test_pool_gather_matches_host_batches(small_ds):
    """Device gather of a RoundPlan is bitwise identical to the numpy path
    (same RNG stream, same cyclic fill, same step mask)."""
    pool = ClientPool(small_ds)
    clients = np.array([3, 0, 7, 11])
    r_host, r_pool = np.random.default_rng(5), np.random.default_rng(5)
    host = small_ds.sample_round_batches(r_host, clients, 3, 4)
    dev = pool.gather(pool.plan(r_pool, clients, 3, 4))
    assert set(host) == set(dev)
    for k in host:
        assert np.array_equal(host[k], np.asarray(dev[k])), k
    # the two paths consumed the RNG identically (streams still in lockstep)
    assert r_host.integers(1 << 30) == r_pool.integers(1 << 30)


def _image_ds(n_clients=10, seed=0):
    """Clients of (8, 8, 3) images: a multi-dimensional example shape."""
    rng = np.random.default_rng(seed)
    data = []
    for _ in range(n_clients):
        n = int(rng.integers(3, 12))
        data.append({"x": rng.normal(size=(n, 8, 8, 3)).astype(np.float32),
                     "y": rng.integers(0, 5, n).astype(np.int32)})
    return FederatedDataset(data, num_classes=5, input_dim=192)


def check_image_pool(mesh, client_axis="data"):
    """Every pool buffer is row-major at its device shape, with each example
    contiguous, and a gather returns exactly numpy's fancy-index of the
    padded host data, in the example shape."""
    ds = _image_ds()
    pool = ClientPool(ds, mesh=mesh, client_axis=client_axis)
    m = pool.max_examples
    rows = pool.buffers["x"].shape[0]
    assert pool.buffers["x"].shape == device_shape((rows, m, 8 * 8 * 3), np.float32)
    assert pool.buffers["y"].shape == device_shape((rows, m), np.int32)
    for k, buf in pool.buffers.items():
        assert buf.format.layout.major_to_minor == tuple(range(buf.ndim)), k
    plan = pool.plan(np.random.default_rng(3), np.array([7, 0, 9, 3]), 3, 4)
    got = pool.gather(plan)
    for k in ("x", "y"):
        first = ds.client_data[0][k]
        padded = np.zeros((ds.n_clients, m) + first.shape[1:], first.dtype)
        for i, d in enumerate(ds.client_data):
            padded[i, : len(d[k])] = d[k]
        want = padded[plan.clients[:, None, None], plan.take]
        assert np.array_equal(np.asarray(got[k]), want), k
    assert np.array_equal(np.asarray(got["_step_mask"]), plan.step_mask)
    # the whole pool went up: each client's rows read back, padding zero
    x = np.asarray(pool.buffers["x"])
    for i, d in enumerate(ds.client_data):
        n = len(d["x"])
        assert np.array_equal(x[i, :n, :192], d["x"].reshape(n, 192)), i
        assert not x[i, n:].any() and not x[i, :, 192:].any(), i


POOL_4_DEVICES = """
import sys
sys.path.insert(0, {tests!r})
import jax
from repro.sim import pool
from test_sim import check_image_pool
pool.UPLOAD_BYTES = {upload_bytes}
check_image_pool(jax.make_mesh((4,), ("data",)))
print("POOL-4-OK")
"""


@pytest.mark.parametrize("where", ["single", "single-row-blocks", "sharded",
                                   "sharded-4-devices"])
def test_pool_row_major_gather_of_images(where, monkeypatch):
    """The pool's layout rule on a multi-dimensional example shape, on one
    device (uploaded whole, and in blocks of 3 of its 10 image rows, the
    last block overlapping), sharded on the live devices' client mesh, and
    sharded over four forced host devices in blocks of 2 of each shard's 3
    rows (a subprocess)."""
    ds = _image_ds()
    row_bytes = max(len(d["x"]) for d in ds.client_data) * 8 * 8 * 3 * 4
    if where == "single":
        check_image_pool(None)
    elif where == "single-row-blocks":
        monkeypatch.setattr(pool_mod, "UPLOAD_BYTES", 3 * row_bytes)
        check_image_pool(None)
    elif where == "sharded":
        fl = FLConfig(n_clients=4, expected_clients=2)
        check_image_pool(build_client_mesh(fl), fl.client_axis)
    else:
        tests = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4",
                   PYTHONPATH=os.path.join(os.path.dirname(tests), "src"))
        code = POOL_4_DEVICES.format(tests=tests, upload_bytes=2 * row_bytes)
        out = subprocess.run([sys.executable, "-c", code],
                             env=env, capture_output=True, text=True, timeout=300)
        assert "POOL-4-OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.parametrize(
    "fl_kw",
    [{}, {"compression": "randk", "compression_param": 0.5, "availability": 0.7}],
    ids=["plain", "randk+avail"],
)
def test_sim_mask_parity_with_legacy_loop(small_ds, fl_kw):
    """Acceptance gate: for a fixed seed, every driver mode draws bitwise
    identical per-round masks to the legacy trainer loop, and ends at
    allclose parameters.  rounds=5 with rounds_per_scan=2 exercises the
    scan path's remainder block."""
    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1,
                  scan_group=2, cache_groups=2, **fl_kw)
    rounds, bs, seed = 5, 4, 3
    legacy_params, legacy_masks = _legacy_loop(small_ds, init, loss, fl, rounds, bs, seed)
    for mode in MODES:
        params, led = run_simulation(
            small_ds, init, loss, fl, rounds, batch_size=bs, mode=mode,
            rounds_per_scan=2, seed=seed,
        )
        for k in range(rounds):
            assert np.array_equal(legacy_masks[k], np.asarray(led.masks[k])), (mode, k)
        for a, b in zip(
            jax.tree_util.tree_leaves(legacy_params), jax.tree_util.tree_leaves(params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, err_msg=mode
            )


def test_run_training_wrapper_parity(small_ds):
    """The trainer is now a thin wrapper: every mode returns the same History
    scalar series, and the eval curve is rectangular (acc_rounds + acc)."""
    init, loss, acc = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1)
    ev = {"x": jnp.zeros((4, small_ds.input_dim)), "y": jnp.zeros((4,), jnp.int32)}
    hists = {}
    for mode in ("host", "prefetch"):
        _, hists[mode] = run_training(
            small_ds, init, loss, fl, rounds=3, batch_size=4,
            eval_fn=jax.jit(acc), eval_batch=ev, eval_every=2, seed=4, mode=mode,
        )
    np.testing.assert_array_equal(hists["host"].sent, hists["prefetch"].sent)
    np.testing.assert_allclose(hists["host"].loss, hists["prefetch"].loss, atol=1e-6)
    h = hists["prefetch"]
    assert h.acc_rounds == [0, 2]  # eval_every=2 with rounds=3
    assert len(h.acc) == 2
    arrays = h.as_arrays()
    for name, arr in arrays.items():
        assert arr.dtype != object, name  # nothing ragged anywhere


def test_driver_validates_cohort_size(small_ds):
    """fl.n_clients > pool used to crash deep inside rng.choice with an
    opaque numpy error; now the driver (and the trainer wrapper) raise a
    ValueError naming both numbers."""
    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=40, expected_clients=3)
    with pytest.raises(ValueError, match=r"n_clients=40 .* 24 clients"):
        run_simulation(small_ds, init, loss, fl, 1)
    with pytest.raises(ValueError, match=r"n_clients=40 .* 24 clients"):
        run_training(small_ds, init, loss, fl, rounds=1)


def test_data_size_weights_wired(small_ds):
    """Regression (the legacy loop ignored fl.weights == 'data_size'): the
    driver passes each cohort's normalized sizes slice to the engine."""
    init, loss, _ = _model(small_ds)
    kw = dict(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1)
    _, led = run_simulation(
        small_ds, init, loss, FLConfig(weights="data_size", **kw), 1,
        batch_size=4, mode="host", seed=2,
    )
    # replicate round 0 by hand with the cohort's size-proportional weights
    fl = FLConfig(weights="data_size", **kw)
    rng = np.random.default_rng(2)
    key = jax.random.PRNGKey(2)
    params = init(jax.random.fold_in(key, 1))
    clients = rng.choice(small_ds.n_clients, size=fl.n_clients, replace=False)
    w = client_weights(fl, jnp.asarray(np.asarray(small_ds.sizes())[clients]))
    assert float(jnp.std(w)) > 0  # the unbalanced pool gives non-uniform weights
    batch = small_ds.sample_round_batches(rng, clients, fl.local_steps, 4)
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    step = jax.jit(RoundEngine(loss, fl, None).make_step())
    _, _, m = step(params, (), batch, w, jax.random.fold_in(key, 1000))
    np.testing.assert_array_equal(np.asarray(m.norms), led.norms[0])
    # and the old uniform-weights behaviour is measurably different
    _, led_uni = run_simulation(
        small_ds, init, loss, FLConfig(**kw), 1, batch_size=4, mode="host", seed=2
    )
    assert not np.allclose(led.norms[0], led_uni.norms[0])


def test_scan_mode_keeps_eval_grid(small_ds):
    """Regression (PR 4 follow-up): scan mode used to evaluate once per
    block; the driver now aligns block boundaries to the eval_every grid, so
    all three modes record identical acc_rounds for eval_every > 1."""
    init, loss, acc = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1)
    ev = {"x": jnp.zeros((4, small_ds.input_dim)), "y": jnp.zeros((4,), jnp.int32)}
    leds = {}
    for mode in MODES:
        _, leds[mode] = run_simulation(
            small_ds, init, loss, fl, 7, batch_size=4, mode=mode,
            rounds_per_scan=3, eval_fn=jax.jit(acc), eval_batch=ev,
            eval_every=3, seed=5,
        )
    assert leds["host"].acc_rounds == [0, 3, 6]
    for mode in ("prefetch", "scan"):
        assert leds[mode].acc_rounds == leds["host"].acc_rounds, mode
        assert len(leds[mode].acc) == len(leds[mode].acc_rounds), mode
    np.testing.assert_allclose(leds["prefetch"].acc, leds["host"].acc, atol=1e-6)
    # the eval-aligned blocks change nothing about the round stream itself
    for mode in ("prefetch", "scan"):
        for k in range(7):
            assert np.array_equal(leds["host"].masks[k], leds[mode].masks[k])


def test_sharded_scenario_cell(small_ds):
    """The mesh column of the grid: a sharded cell (compression included)
    runs end to end through run_scenario — shard_map round + sharded
    ClientPool — with a schema-valid ledger and masks bitwise identical to
    the same cell without the mesh; scan mode is rejected with the
    documented error."""
    name = "femnist1-fedavg-aocs-shard-randk"
    _, led = run_scenario(name, reduced=True, mode="prefetch", rounds=2)
    validate_ledger(led.to_json())
    assert led.workload["mesh_axis_size"] >= 1
    unsharded = get_scenario(name).with_(sharded=False)
    _, led2 = run_scenario(unsharded, reduced=True, mode="prefetch", rounds=2)
    for k in range(2):
        assert np.array_equal(np.asarray(led.masks[k]), np.asarray(led2.masks[k]))
    assert led.uplink_bits == led2.uplink_bits  # identical compression bill
    with pytest.raises(ValueError, match="mesh"):
        run_scenario(name, reduced=True, mode="scan", rounds=1)


def test_scenario_grid_smoke():
    """Every registered scenario runs 2 reduced rounds end to end with finite
    loss and a schema-valid ledger (the ISSUE's grid acceptance check)."""
    names = list_scenarios()
    assert len(names) >= 30  # Sec. 4 grid + the system-realism cells
    for name in names:
        _, led = run_scenario(name, reduced=True, mode="prefetch", rounds=2)
        assert np.all(np.isfinite(led.loss)), name
        validate_ledger(led.to_json())
        assert led.scenario == name + "-reduced"


def test_scenario_registry_lookup():
    sc = get_scenario("femnist1-fedavg-aocs")
    assert sc.fl.sampler == "aocs" and sc.dataset == "femnist1"
    with pytest.raises(KeyError, match="registered:"):
        get_scenario("nope")


def test_ledger_artifact_and_schema(small_ds, tmp_path):
    """The driver writes a schema-3 JSON artifact that validates, and
    validate_ledger rejects the failure shapes it exists to catch."""
    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=1, lr_local=0.1)
    path = str(tmp_path / "sim" / "run.json")
    _, led = run_simulation(
        small_ds, init, loss, fl, 2, batch_size=4, mode="scan",
        rounds_per_scan=2, seed=0, artifact=path,
    )
    doc = json.load(open(path))
    validate_ledger(doc)
    assert doc["workload"]["rounds_per_scan"] == 2
    assert doc["metrics"]["downlink_bits"][-1] > 0
    bad = json.loads(json.dumps(doc))
    bad["schema"] = 0
    with pytest.raises(ValueError, match="schema"):
        validate_ledger(bad)
    bad = json.loads(json.dumps(doc))
    bad["metrics"]["loss"] = bad["metrics"]["loss"][:-1]
    with pytest.raises(ValueError, match="ragged"):
        validate_ledger(bad)
    bad = json.loads(json.dumps(doc))
    del bad["metrics"]["downlink_bits"]
    with pytest.raises(ValueError, match="downlink_bits"):
        validate_ledger(bad)


def test_sim_rejects_bad_mode(small_ds):
    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3)
    with pytest.raises(ValueError, match="sim mode"):
        run_simulation(small_ds, init, loss, fl, 1, mode="warp")
    with pytest.raises(ValueError, match="rounds_per_scan"):
        run_simulation(small_ds, init, loss, fl, 1, mode="scan", rounds_per_scan=0)


# --- the client-state layer (system-realism PR) ---------------------------

def _strip_timing(doc, mode_identity=False):
    """Ledger JSON minus the wall-clock fields — everything that must be
    byte-identical across repeat runs.  ``mode_identity=True`` also drops
    the fields that legitimately name the execution policy (``mode`` and
    the mode-specific workload keys), leaving what must additionally be
    byte-identical ACROSS driver modes."""
    doc = json.loads(json.dumps(doc))
    doc.pop("wall_s", None)
    doc.pop("rounds_per_sec", None)
    doc.get("metrics", {}).pop("wall_ms", None)  # per-round wall clock (schema 3)
    if mode_identity:
        doc.pop("mode", None)
        for k in ("pool_bytes", "rounds_per_scan"):
            doc.get("workload", {}).pop(k, None)
    return doc


def test_straggler_cell_deterministic_across_modes():
    """Determinism regression (ISSUE 7 satellite): the same seed produces a
    byte-identical ledger JSON — masks included, timing excluded — for a
    straggler cell in ALL three driver modes, so the client-state chain,
    deadline and dropout draws are a pure function of the seed everywhere."""
    docs, reps = {}, {}
    for mode in MODES:
        _, led = run_scenario("femnist1-fedavg-aocs-straggler", reduced=True,
                              mode=mode, rounds=4, rounds_per_scan=2, seed=11)
        validate_ledger(led.to_json())
        docs[mode] = json.dumps(_strip_timing(led.to_json(include_masks=True)),
                                sort_keys=True)
        _, led2 = run_scenario("femnist1-fedavg-aocs-straggler", reduced=True,
                               mode=mode, rounds=4, rounds_per_scan=2, seed=11)
        reps[mode] = json.dumps(_strip_timing(led2.to_json(include_masks=True)),
                                sort_keys=True)
    for mode in MODES:
        assert docs[mode] == reps[mode], f"{mode}: same seed, different ledger"
        same = json.dumps(_strip_timing(json.loads(docs[mode]),
                                        mode_identity=True), sort_keys=True)
        ref = json.dumps(_strip_timing(json.loads(docs["host"]),
                                       mode_identity=True), sort_keys=True)
        assert same == ref, f"{mode}: diverged from host"
    # the system counters actually fired (this cell exists to exercise them)
    doc = json.loads(docs["host"])
    assert sum(doc["metrics"]["over_selected"]) > 0
    assert all(v >= 0 for v in doc["metrics"]["deadline_misses"])
    assert all(v >= 0 for v in doc["metrics"]["dropouts"])


def test_straggler_cell_identical_across_modes_past_a_ledger_chunk():
    """The cross-mode identity above over more rounds than one chunk of the
    ledger's device read (LEDGER_CHUNK), ending mid-chunk: the chunked read
    (host, prefetch) and the stacked blocks (scan) splice byte-identical
    ledgers."""
    rounds = LEDGER_CHUNK + 3
    docs = {}
    for mode in MODES:
        _, led = run_scenario("femnist1-fedavg-aocs-straggler", reduced=True,
                              mode=mode, rounds=rounds, rounds_per_scan=8, seed=11)
        validate_ledger(led.to_json())
        assert len(led.loss) == len(led.masks) == rounds
        docs[mode] = json.dumps(_strip_timing(led.to_json(include_masks=True),
                                              mode_identity=True), sort_keys=True)
    assert docs["prefetch"] == docs["host"]
    assert docs["scan"] == docs["host"]


@pytest.mark.parametrize("mode", MODES)
def test_ledger_device_reads_do_not_grow_with_rounds(small_ds, mode, monkeypatch):
    """The ledger reads the device once a call, however many rounds it ran:
    one ``ledger_read`` span holding one ``jax.device_get``, at 5 rounds and
    at 2 x LEDGER_CHUNK + 3, and no other device read (``np.asarray``,
    ``float()``) inside the ``ledger`` span."""
    from jax._src.array import ArrayImpl

    open_spans, seen = [], {}
    real_get, real_span, real_value = jax.device_get, drv.obs_span, ArrayImpl._value
    real_asarray = np.asarray

    def device_get(x):
        if "ledger" in open_spans:
            seen["gets"] += 1
        open_spans.append("device_get")
        try:
            return real_get(x)
        finally:
            open_spans.pop()

    @contextlib.contextmanager
    def span(name, sink=None):
        seen[name] = seen.get(name, 0) + 1
        open_spans.append(name)
        try:
            with real_span(name, sink) as s:
                yield s
        finally:
            open_spans.pop()

    def loose(x):
        if (isinstance(x, jax.Array) and "ledger" in open_spans
                and "device_get" not in open_spans):
            seen["loose"] += 1

    def asarray(x, *a, **kw):
        loose(x)   # on the CPU np.asarray reads a jax.Array without _value
        return real_asarray(x, *a, **kw)

    def value(self):
        loose(self)
        return real_value.fget(self)

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(drv, "obs_span", span)
    monkeypatch.setattr(np, "asarray", asarray)
    monkeypatch.setattr(ArrayImpl, "_value", property(value))
    init, loss, acc = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=1, lr_local=0.1)
    ev = {"x": jnp.zeros((4, small_ds.input_dim)), "y": jnp.zeros((4,), jnp.int32)}
    for rounds in (5, 2 * LEDGER_CHUNK + 3):
        seen.clear()
        seen.update(gets=0, loose=0)
        _, led = run_simulation(
            small_ds, init, loss, fl, rounds, batch_size=4, mode=mode,
            rounds_per_scan=4, seed=0, eval_fn=jax.jit(acc), eval_batch=ev,
            eval_every=50,
        )
        assert len(led.loss) == len(led.masks) == rounds
        assert len(led.acc) == len(range(0, rounds, 50)) + 1
        assert seen["ledger"] == seen["ledger_read"] == seen["gets"] == 1, (rounds, seen)
        assert seen["loose"] == 0, (rounds, seen)


def test_straggler_shard_cell_matches_unsharded():
    """The mesh leg of the straggler matrix: the sharded straggler cell's
    masks AND system counters are bitwise identical to the same cell without
    the mesh (the shard_map round threads the trace replicated)."""
    name = "femnist1-fedavg-aocs-straggler-shard"
    _, led = run_scenario(name, reduced=True, mode="prefetch", rounds=3)
    validate_ledger(led.to_json())
    unsharded = get_scenario(name).with_(sharded=False)
    _, led2 = run_scenario(unsharded, reduced=True, mode="prefetch", rounds=3)
    for k in range(3):
        assert np.array_equal(np.asarray(led.masks[k]), np.asarray(led2.masks[k]))
    assert led.over_selected == led2.over_selected
    assert led.deadline_misses == led2.deadline_misses
    assert led.dropouts == led2.dropouts


def test_threshold_cell_deterministic_across_modes():
    """Golden-ledger determinism regression (ISSUE 8 satellite): a stateful
    zoo-sampler cell produces a byte-identical ledger JSON — masks included,
    timing excluded — across repeat runs AND across all three driver modes,
    so the SamplerState carry (jitted feedback in host/prefetch, lax.scan
    carry slot in scan mode) is a pure function of the seed everywhere."""
    name = "femnist1-fedavg-threshold"
    docs, reps = {}, {}
    for mode in MODES:
        _, led = run_scenario(name, reduced=True, mode=mode, rounds=4,
                              rounds_per_scan=2, seed=11)
        validate_ledger(led.to_json())
        docs[mode] = json.dumps(_strip_timing(led.to_json(include_masks=True)),
                                sort_keys=True)
        _, led2 = run_scenario(name, reduced=True, mode=mode, rounds=4,
                               rounds_per_scan=2, seed=11)
        reps[mode] = json.dumps(_strip_timing(led2.to_json(include_masks=True)),
                                sort_keys=True)
    for mode in MODES:
        assert docs[mode] == reps[mode], f"{mode}: same seed, different ledger"
        same = json.dumps(_strip_timing(json.loads(docs[mode]),
                                        mode_identity=True), sort_keys=True)
        ref = json.dumps(_strip_timing(json.loads(docs["host"]),
                                       mode_identity=True), sort_keys=True)
        assert same == ref, f"{mode}: diverged from host"
    # the threshold's cold start actually fired: round 1 sends everyone
    # (8/8 on the reduced cell)
    doc = json.loads(docs["host"])
    assert doc["metrics"]["sent"][0] == doc["fl"]["n_clients"]


def test_ledger_schema2_system_series(small_ds, tmp_path):
    """validate_ledger's schema-2 additions: the system-counter series are
    required, length-checked and sign-checked, and survive a JSON
    round-trip."""
    from repro.sim import SystemConfig

    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=1, lr_local=0.1,
                  over_select=1.5)
    system = SystemConfig(p_up=0.6, p_down=0.3, latency_sigma=0.5,
                          deadline=2.0, drop_prob=0.2)
    path = str(tmp_path / "run.json")
    _, led = run_simulation(
        small_ds, init, loss, fl, 3, batch_size=4, mode="host", seed=1,
        system=system, artifact=path,
    )
    doc = json.load(open(path))
    validate_ledger(doc)
    assert doc["workload"]["system"]["drop_prob"] == 0.2
    for series in ("over_selected", "deadline_misses", "dropouts"):
        assert len(doc["metrics"][series]) == 3, series
        bad = json.loads(json.dumps(doc))
        del bad["metrics"][series]
        with pytest.raises(ValueError, match=series):
            validate_ledger(bad)
        bad = json.loads(json.dumps(doc))
        bad["metrics"][series][0] = -1
        with pytest.raises(ValueError, match="negative"):
            validate_ledger(bad)


def test_sim_rejects_system_with_scalar_availability(small_ds):
    """fl.availability < 1 and a SystemConfig are two models of the same
    thing — the driver refuses the ambiguous combination."""
    from repro.sim import SystemConfig

    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, availability=0.7)
    with pytest.raises(ValueError, match="availability"):
        run_simulation(small_ds, init, loss, fl, 1,
                       system=SystemConfig(p_up=0.5, p_down=0.5))
