"""Checkpoint & resume subsystem: the atomic versioned checkpoint layer
(validation errors that NAME the offending key, latest-complete selection,
keep-pruning, the legacy flat layout), and the driver's full-fidelity
resume gate — an interrupted run restored from its RoundCheckpoint must
finish with bitwise-identical params and a byte-identical ledger (minus
wall-clock) vs the uninterrupted run, in all three driver modes, with a
stateful sampler, Markov client-state, randk compression, a server
optimizer, and under a mesh; plus crash-injection (SIGKILL mid-run) and
the launch/train.py full-state checkpoint regression (an earlier version
saved params only, silently dropping the server-opt state)."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointConfig,
    available_steps,
    latest_step,
    load_round,
    read_meta,
    restore,
    restore_subtree,
    save,
)
from repro.configs.base import FLConfig
from repro.data import femnist_like
from repro.models.simple import mlp_classifier
from repro.optim import sgd
from repro.sim import run_simulation
from repro.sim.driver import build_client_mesh
from repro.sim.pool import SystemConfig

MODES = ("host", "prefetch", "scan")


@pytest.fixture(scope="module")
def small_ds():
    return femnist_like(
        dataset_id=1, n_clients=24, dim=48, num_classes=10, base_examples=24, seed=0
    )


def _model(ds):
    return mlp_classifier(ds.input_dim, ds.num_classes, hidden=16)


def _strip_timing(doc):
    doc = json.loads(json.dumps(doc))
    doc.pop("wall_s")
    doc.pop("rounds_per_sec")
    doc["metrics"].pop("wall_ms")
    return json.dumps(doc, sort_keys=True)


def _tree():
    return {
        "w": np.arange(6, dtype=np.float32).reshape(2, 3),
        "b": {"inner": np.ones(4, dtype=np.int32)},
    }


# ---------------------------------------------------------------- ckpt layer


def test_versioned_layout_and_latest_complete(tmp_path):
    """Steps coexist under step-XXXXXXXX dirs; a torn step (the crash-mid-
    save failure mode) is skipped and restore falls back to the newest
    COMPLETE checkpoint."""
    root = str(tmp_path / "ck")
    save(root, _tree(), step=3)
    save(root, _tree(), step=7)
    assert available_steps(root) == [3, 7]
    # tear step 7 the way a mid-np.savez crash would: truncate the payload
    with open(os.path.join(root, "step-00000007", "leaves.npz"), "wb") as f:
        f.write(b"PK\x03\x04garbage")
    assert available_steps(root) == [3]
    assert latest_step(root) == 3
    _, step = restore(root, _tree())
    assert step == 3
    # an orphaned staging dir (crash before the atomic publish) is invisible
    os.makedirs(os.path.join(root, ".tmp-step-00000009-123"))
    assert available_steps(root) == [3]
    # pinning an explicit step dir still works
    _, step = restore(os.path.join(root, "step-00000003"), _tree())
    assert step == 3


def test_restore_errors_name_offending_key(tmp_path):
    """Structure/dtype/shape mismatches raise ValueError NAMING the key —
    never a bare assert (optimised out under python -O), never a silent
    .astype coercion."""
    root = str(tmp_path / "ck")
    save(root, _tree(), step=0)
    bad_dtype = _tree()
    bad_dtype["b"]["inner"] = np.ones(4, dtype=np.float32)
    with pytest.raises(ValueError, match=r"dtype.*\['b'\]\['inner'\]"):
        restore(root, bad_dtype)
    bad_shape = _tree()
    bad_shape["w"] = np.zeros((3, 3), np.float32)
    with pytest.raises(ValueError, match=r"shape.*\['w'\]"):
        restore(root, bad_shape)
    bad_keys = _tree()
    bad_keys["extra"] = np.zeros(1)
    with pytest.raises(ValueError, match="structure mismatch"):
        restore(root, bad_keys)


def test_keep_prunes_old_steps(tmp_path):
    root = str(tmp_path / "ck")
    for s in range(1, 6):
        save(root, _tree(), step=s, keep=2)
    assert available_steps(root) == [4, 5]


def test_legacy_flat_layout_still_restores(tmp_path):
    """Pre-PR checkpoints put index.json directly in the directory; they
    must keep restoring (and serve's params loader must read them)."""
    root = str(tmp_path / "ck")
    save(root, _tree(), step=5)
    flat = str(tmp_path / "flat")
    shutil.copytree(os.path.join(root, "step-00000005"), flat)
    tree, step = restore(flat, _tree())
    assert step == 5
    np.testing.assert_array_equal(tree["w"], _tree()["w"])


def test_restore_subtree_pulls_params_only(tmp_path):
    root = str(tmp_path / "ck")
    full = {"params": _tree(), "opt_state": {"m": np.zeros(3, np.float32)}}
    save(root, full, step=2, meta={"round": 2})
    sub, step = restore_subtree(root, _tree(), "['params']")
    assert step == 2
    np.testing.assert_array_equal(sub["b"]["inner"], _tree()["b"]["inner"])
    meta, _ = read_meta(root)
    assert meta["round"] == 2
    with pytest.raises(ValueError, match="dtype"):
        bad = _tree()
        bad["w"] = bad["w"].astype(np.float16)
        restore_subtree(root, bad, "['params']")


# ------------------------------------------------------------- resume parity

# the acceptance matrix: every driver mode x {stateful sampler + Markov
# client-state, randk compression, server momentum}
VARIANTS = {
    "threshold+markov": (
        {"sampler": "threshold"}, SystemConfig(), None),
    "randk": (
        {"compression": "randk", "compression_param": 0.5}, None, None),
    "momentum": ({}, None, "momentum"),
}


def _run(ds, rounds, mode, fl_kw, system, opt_name, **kw):
    init, loss, acc = _model(ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1,
                  scan_group=2, cache_groups=2, **fl_kw)
    ev = {"x": jnp.zeros((4, ds.input_dim)), "y": jnp.zeros((4,), jnp.int32)}
    opt = sgd(0.5, momentum=0.9) if opt_name == "momentum" else None
    return run_simulation(
        ds, init, loss, fl, rounds, batch_size=4, mode=mode,
        rounds_per_scan=3, seed=3, system=system, server_opt=opt,
        eval_fn=jax.jit(acc), eval_batch=ev, eval_every=3, **kw,
    )


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_resume_parity(small_ds, tmp_path, mode, variant):
    """The tentpole gate: (a) checkpointing must not perturb the run, and
    (b) a run resumed from an INTERMEDIATE checkpoint finishes with bitwise
    params and a byte-identical ledger minus wall-clock.  ckpt_every=4 sits
    off the rounds_per_scan=3 grid on purpose, so scan mode exercises the
    checkpoint-boundary block alignment (and the eval_every=3 grid composes
    with both)."""
    fl_kw, system, opt = VARIANTS[variant]
    rounds = 7
    p_ref, led_ref = _run(small_ds, rounds, mode, fl_kw, system, opt)
    ref = _strip_timing(led_ref.to_json())
    d = str(tmp_path / "ck")
    _, led_ck = _run(small_ds, rounds, mode, fl_kw, system, opt,
                     checkpoint=CheckpointConfig(d, every=4))
    # (a) writing checkpoints changed nothing but wall-clock
    assert _strip_timing(led_ck.to_json()) == ref
    assert available_steps(d) == [4, 7]
    # (b) resume from the intermediate (NOT final) step, explicitly pinned
    p_res, led_res = _run(small_ds, rounds, mode, fl_kw, system, opt,
                          resume=os.path.join(d, "step-00000004"))
    assert _strip_timing(led_res.to_json()) == ref
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_under_mesh(small_ds, tmp_path):
    """Restore-under-mesh: the shard_map round accepts restored (host) params
    and continues bitwise.  Runs on however many devices the container has
    (CI adds a 4-emulated-device leg via tools/check_resume.py)."""
    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1)
    mesh = build_client_mesh(fl)
    kw = dict(batch_size=4, mode="prefetch", seed=3, mesh=mesh)
    p_ref, led_ref = run_simulation(small_ds, init, loss, fl, 6, **kw)
    d = str(tmp_path / "ck")
    run_simulation(small_ds, init, loss, fl, 4, checkpoint=CheckpointConfig(d, every=2), **kw)
    p_res, led_res = run_simulation(small_ds, init, loss, fl, 6, resume=d, **kw)
    assert _strip_timing(led_res.to_json()) == _strip_timing(led_ref.to_json())
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fingerprint_mismatch_rejected(small_ds, tmp_path):
    """A checkpoint never resumes into a different experiment: the config
    fingerprint gate fires and the error NAMES the differing keys."""
    fl_kw, system, opt = VARIANTS["threshold+markov"]
    d = str(tmp_path / "ck")
    _run(small_ds, 4, "host", fl_kw, system, opt,
         checkpoint=CheckpointConfig(d, every=2))
    with pytest.raises(ValueError, match="fingerprint.*seed"):
        init, loss, acc = _model(small_ds)
        fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2,
                      lr_local=0.1, scan_group=2, cache_groups=2, **fl_kw)
        run_simulation(small_ds, init, loss, fl, 8, batch_size=4, mode="host",
                       seed=4, system=system, eval_fn=jax.jit(acc),
                       eval_batch={"x": jnp.zeros((4, small_ds.input_dim)),
                                   "y": jnp.zeros((4,), jnp.int32)},
                       eval_every=3, resume=d)


def test_resume_at_or_past_rounds_rejected(small_ds, tmp_path):
    fl_kw, system, opt = VARIANTS["randk"]
    d = str(tmp_path / "ck")
    _run(small_ds, 4, "host", fl_kw, system, opt,
         checkpoint=CheckpointConfig(d, every=4))
    with pytest.raises(ValueError, match="raise rounds"):
        _run(small_ds, 4, "host", fl_kw, system, opt, resume=d)


def test_params_only_checkpoint_cannot_resume(small_ds, tmp_path):
    """A legacy params-only checkpoint is rejected up front — it cannot
    reproduce the trajectory (no opt/RNG/sampler state), so resuming from
    one must be an error, not a silently different run."""
    d = str(tmp_path / "ck")
    save(d, _tree(), step=3)
    with pytest.raises(ValueError, match="not a RoundCheckpoint"):
        load_round(d, params=_tree(), opt_state=())
    fl_kw, system, opt = VARIANTS["randk"]
    with pytest.raises(ValueError, match="not a RoundCheckpoint"):
        _run(small_ds, 6, "host", fl_kw, system, opt, resume=d)


_CRASH_CHILD = """
import sys
import jax
from repro.checkpoint import CheckpointConfig
from repro.configs.base import FLConfig
from repro.data import femnist_like
from repro.models.simple import mlp_classifier
from repro.sim import run_simulation

ds = femnist_like(dataset_id=1, n_clients=24, dim=48, num_classes=10,
                  base_examples=24, seed=0)
init, loss, _ = mlp_classifier(ds.input_dim, ds.num_classes, hidden=16)
fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1,
              sampler="threshold")
run_simulation(ds, init, loss, fl, 100000, batch_size=4, mode="host", seed=3,
               checkpoint=CheckpointConfig(sys.argv[1], every=2))
"""


def test_crash_injection_sigkill(small_ds, tmp_path):
    """Crash-injection: SIGKILL a checkpointing subprocess mid-run, resume
    from whatever complete checkpoint survived, and finish — the result must
    equal a straight-through run of the same length."""
    d = str(tmp_path / "ck")
    script = tmp_path / "child.py"
    script.write_text(_CRASH_CHILD)
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(sys.path),
               JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, str(script), d], env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if (latest_step(d) or 0) >= 4:
                break
            if proc.poll() is not None:
                pytest.fail(f"child exited early: rc={proc.returncode}")
            time.sleep(0.05)
        else:
            pytest.fail("child never reached a round-4 checkpoint")
        proc.send_signal(signal.SIGKILL)
    finally:
        proc.wait(timeout=30)
    k0 = latest_step(d)
    assert k0 is not None and k0 >= 4
    rounds = k0 + 3
    init, loss, _ = _model(small_ds)
    fl = FLConfig(n_clients=8, expected_clients=3, local_steps=2, lr_local=0.1,
                  sampler="threshold")
    p_ref, led_ref = run_simulation(
        small_ds, init, loss, fl, rounds, batch_size=4, mode="host", seed=3)
    p_res, led_res = run_simulation(
        small_ds, init, loss, fl, rounds, batch_size=4, mode="host", seed=3,
        resume=d)
    assert _strip_timing(led_res.to_json()) == _strip_timing(led_ref.to_json())
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------- launch/train.py CLI


def _round_lines(text):
    import re

    return [re.sub(r"\(\d+\.\d+s\)", "", line)
            for line in text.splitlines() if line.startswith("[round")]


def test_train_cli_checkpoints_full_state(tmp_path, capsys, monkeypatch):
    """Regression for the params-only --checkpoint bug: the training CLI's
    checkpoint must carry the server-opt state (and RNG/sampler/client
    state), and a --resume run must print the exact round lines (loss,
    alpha, sent, bits) the uninterrupted run prints — momentum makes a
    dropped opt state visibly diverge."""
    from repro.launch import train

    # train.main turns the persistent compile cache on unless this variable
    # is set; set after jax read it, it keeps this test process uncached
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))

    d = str(tmp_path / "ck")
    base = ["--arch", "llama3-8b-reduced", "--rounds", "4", "--clients", "2",
            "--expected", "1", "--batch", "1", "--seq", "8",
            "--server-opt", "momentum", "--sampler", "threshold"]
    train.main(base)
    ref = _round_lines(capsys.readouterr().out)
    train.main(base[:3] + ["2"] + base[4:]
               + ["--checkpoint", d, "--ckpt-every", "2"])
    first = _round_lines(capsys.readouterr().out)
    idx = json.load(open(os.path.join(d, "step-00000002", "index.json")))
    # the bug: only ['params'] leaves were saved — opt state dropped silently
    assert any(k.startswith("['opt_state']") for k in idx["keys"])
    assert any(k.startswith("['sampler_state']") for k in idx["keys"])
    assert idx["meta"]["round"] == 2
    assert "rng_state" in idx["meta"]
    train.main(base + ["--resume", d])
    resumed = _round_lines(capsys.readouterr().out)
    assert first + resumed == ref
    # flag drift is rejected, not silently resumed into
    with pytest.raises(SystemExit, match="fingerprint"):
        train.main(base[:-1] + ["uniform", "--resume", d])


def test_serve_load_params_both_layouts(tmp_path):
    """serve --restore reads params out of a full-state checkpoint (subtree)
    and out of a legacy params-only checkpoint (whole tree)."""
    from repro.launch.serve import load_params

    params = _tree()
    full_dir = str(tmp_path / "full")
    save(full_dir, {"params": params, "opt_state": {"m": np.zeros(2)}}, step=9)
    got, step = load_params(full_dir, _tree())
    assert step == 9
    np.testing.assert_array_equal(got["w"], params["w"])
    legacy_dir = str(tmp_path / "legacy")
    save(legacy_dir, params, step=1)
    got, step = load_params(legacy_dir, _tree())
    assert step == 1
    np.testing.assert_array_equal(got["b"]["inner"], params["b"]["inner"])
