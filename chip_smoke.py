"""Smoke run of the OCS round on a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip: kernels, sim driver, mamba2-130m
    python chip_smoke.py --four-chips  # four chips: the sharded round only

Phases, in one process (a chip belongs to one process at a time):

1. device check — a TPU, or exit non-zero (JAX falls back to the CPU on
   its own when the TPU fails to start; this script never does);
2. kernels — the six norm / Eq. 2 Pallas kernels, compiled, at the shapes
   the later phases stream, against the f32 oracles in ``kernels/ref.py``;
   the sim cell's compiled round step must hold a ``tpu_custom_call``;
3. sim driver — ``femnist1-fedavg-aocs-pallas`` at full size (the paper's
   FEMNIST MLP, 32-client cohort from a 96-client pool) in host, prefetch
   and scan mode through ``repro.sim.driver.run_scenario``: masks bitwise
   equal across modes in every round, round 0's mask equal to the jnp
   cell's, losses finite;
4. mamba2-130m at its published widths through
   ``repro.launch.train.main`` — scan engine, pallas aggregate, 3 rounds,
   finite loss.

``--four-chips`` runs only ``femnist1-fedavg-aocs-shard`` on a 4-device
client mesh (per-shard kernel + one psum) against the same cell on one
device, both with f32-precision dots: round 0's mask bitwise equal, final
params allclose.

Timings printed here are smoke readings of one run, not benchmark metrics.
Any failed check exits non-zero before the last line, which is the JSON
``{"ok": true, "device": {...}}`` only after every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.compression import apply_compression_flat  # noqa: E402
from repro.fl.engine import RoundEngine  # noqa: E402
from repro.fl.round import client_weights  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.sim.driver import build_client_mesh, run_scenario  # noqa: E402
from repro.sim.scenarios import get_scenario  # noqa: E402

# the sim ledgers each phase writes (run artifacts, never committed)
OUT = os.path.join(ROOT, "chip_smoke_out")
SIM_ROUNDS = 8
ROUNDS_PER_SCAN = 4
MAMBA_ARGV = ["--arch", "mamba2-130m", "--engine", "scan", "--agg-backend",
              "pallas", "--clients", "8", "--expected", "2", "--scan-group", "2",
              "--cache-groups", "4", "--rounds", "3", "--batch", "4", "--seq", "512"]
# (clients, D, update dtype) the phases stream: the sim cell's 32-client
# cohort of the FEMNIST MLP, and one scan group of mamba2-130m
KERNEL_SHAPES = {"femnist-c32": (32, 58_430, jnp.float32),
                 "mamba2-130m-c2": (2, 128_983_488, jnp.bfloat16)}
# kernel-vs-oracle limit: the f32 tolerance of the CPU oracle tests, here
# relative to the sum of |terms| each output element adds up
TOL = 1e-5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"[chip_smoke] FAILED: {msg}")


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def device_check(count: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < count:
        fail(f"{count} TPU devices needed, {len(devs)} found")
    log(f"device {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ------------------------------------------------------------------ kernels

def _err(got, want, terms):
    """Largest ``|kernel - oracle|`` over the sum of ``|terms|`` that output
    element adds up (for the norms that is the norm itself)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    terms = np.maximum(np.asarray(terms, np.float64), np.finfo(np.float32).tiny)
    return float(np.max(np.abs(got - want) / terms))


def check_kernels(name, c, d, dtype, key) -> float:
    """Run the six kernels at ``(c, d)`` against kernels/ref.py; returns the
    largest error (:func:`_err`), which must stay within ``TOL``."""
    kx, ks, km, kg = jax.random.split(key, 4)
    x = jax.random.normal(kx, (c, d), jnp.float32).astype(dtype)
    scale = jnp.where(jax.random.bernoulli(ks, 0.5, (c,)),
                      jax.random.uniform(km, (c,)) * 4.0, 0.0)
    # rand-k material: gain 1/frac on a random tenth of the coordinates
    mats = (jnp.where(jax.random.uniform(kg, (c, d)) < 0.1, 10.0, 0.0),)
    xc = apply_compression_flat(x, "randk", 0.1, *mats)
    xc = xc.astype(dtype).astype(jnp.float32)
    agg_terms = jnp.sum(jnp.abs(scale[:, None] * x.astype(jnp.float32)), axis=0)
    aggc_terms = jnp.sum(jnp.abs(scale[:, None] * xc), axis=0)
    sq_ref = ref.client_sqnorms_ref(x)
    agg_ref = ref.masked_scale_aggregate_ref(x, scale)
    sqc_ref, aggc_ref = ref.compress_norm_scale_aggregate_ref(
        x, scale, mats, "randk", 0.1)

    t0 = time.perf_counter()
    sq, agg = ops.norm_scale_aggregate(x, scale)
    sqc, aggc = ops.compress_norm_scale_aggregate(x, scale, mats, "randk", 0.1)
    shard_sqc, shard_aggc = ops.shard_compress_aggregate(
        x, scale, mats, "randk", 0.1)
    errs = {
        "client_sqnorms": _err(ops.client_sqnorms(x), sq_ref, sq_ref),
        "masked_scale_aggregate": _err(
            ops.masked_scale_aggregate(x, scale), agg_ref, agg_terms),
        "norm_scale_aggregate": max(_err(sq, sq_ref, sq_ref),
                                    _err(agg, agg_ref, agg_terms)),
        "compress_norm_scale_aggregate": max(
            _err(sqc, sqc_ref, sqc_ref), _err(aggc, aggc_ref, aggc_terms)),
        "shard_masked_aggregate": _err(
            ops.shard_masked_aggregate(x, scale), agg_ref, agg_terms),
        "shard_compress_aggregate": max(
            _err(shard_sqc, sqc_ref, sqc_ref),
            _err(shard_aggc, aggc_ref, aggc_terms)),
    }
    secs = time.perf_counter() - t0
    for k, e in errs.items():
        log(f"kernel {k} {name} (c={c}, D={d}, {jnp.dtype(dtype).name}): "
            f"error {e:.3g}")
        if not e <= TOL:
            fail(f"kernel {k} at {name} differs from kernels/ref.py by "
                 f"{e:.3g} > {TOL}")
    log(f"kernels {name}: compile + run {secs:.2f} s")
    return max(errs.values())


def phase_kernels() -> None:
    worst = max(check_kernels(name, *shape, jax.random.PRNGKey(i))
                for i, (name, shape) in enumerate(KERNEL_SHAPES.items()))
    log(f"largest kernel-vs-ref error {worst:.3g} (limit {TOL})")
    step_holds_kernel(get_scenario("femnist1-fedavg-aocs-pallas"))


def step_holds_kernel(scenario) -> None:
    """The scenario's compiled round step contains the Pallas kernel."""
    ds = scenario.build_dataset()
    init_fn, loss_fn, _ = scenario.build_model(ds)
    fl = scenario.fl
    params = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    clients = rng.choice(ds.n_clients, fl.n_clients, replace=False)
    batch = ds.sample_round_batches(rng, clients, fl.local_steps,
                                    scenario.batch_size, True)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
    step = RoundEngine(loss_fn, fl).make_step()
    text = jax.jit(step).lower(
        params, (), batch, client_weights(fl), jax.random.PRNGKey(0), None, None
    ).compile().as_text()
    if "tpu_custom_call" not in text:
        fail(f"the compiled round step of {scenario.name} holds no tpu_custom_call")
    log(f"compiled round step of {scenario.name} holds tpu_custom_call")


# --------------------------------------------------------------- sim driver

def _ledger_line(label, led, unit_rounds):
    first_s = led.wall_ms[0] * unit_rounds / 1e3
    losses = np.asarray(led.loss, np.float64)
    if not np.all(np.isfinite(losses)):
        fail(f"{label}: non-finite loss {led.loss}")
    log(f"{label}: first {'block' if unit_rounds > 1 else 'round'} "
        f"{first_s:.2f} s (incl. compile), steady {led.rounds_per_sec:.2f} "
        f"rounds/s (smoke reading), loss {losses[0]:.4f} -> {losses[-1]:.4f}")


def phase_sim() -> None:
    cell = get_scenario("femnist1-fedavg-aocs-pallas")
    if cell.fl.agg_backend != "pallas":
        fail(f"{cell.name} does not run the pallas aggregate")
    masks = {}
    for mode in ("host", "prefetch", "scan"):
        _, led = run_scenario(
            cell, mode=mode, rounds=SIM_ROUNDS, rounds_per_scan=ROUNDS_PER_SCAN,
            artifact=os.path.join(OUT, f"{cell.name}-{mode}.json"),
        )
        _ledger_line(f"sim {cell.name} {mode}", led,
                     ROUNDS_PER_SCAN if mode == "scan" else 1)
        masks[mode] = np.asarray(led.masks, bool)
    for mode in ("prefetch", "scan"):
        if not np.array_equal(masks[mode], masks["host"]):
            bad = [k for k in range(SIM_ROUNDS)
                   if not np.array_equal(masks[mode][k], masks["host"][k])]
            fail(f"{mode} masks differ from host in rounds {bad}")
    log(f"masks bitwise equal across host/prefetch/scan in all {SIM_ROUNDS} rounds")

    ref_cell = get_scenario("femnist1-fedavg-aocs")
    _, led = run_scenario(ref_cell, mode="host", rounds=SIM_ROUNDS,
                          artifact=os.path.join(OUT, f"{ref_cell.name}-host.json"))
    _ledger_line(f"sim {ref_cell.name} host (jnp)", led, 1)
    jnp_masks = np.asarray(led.masks, bool)
    if not np.array_equal(jnp_masks[0], masks["host"][0]):
        fail("round-0 mask of the pallas cell differs from the jnp cell's")
    agree = sum(np.array_equal(a, b) for a, b in zip(jnp_masks, masks["host"]))
    log(f"pallas vs jnp cell: round-0 mask bitwise equal; masks agree in "
        f"{agree}/{SIM_ROUNDS} rounds")


def phase_mamba() -> None:
    t0 = time.perf_counter()
    hist = train.main(MAMBA_ARGV)
    secs = time.perf_counter() - t0
    if len(hist["loss"]) != 3 or not np.all(np.isfinite(hist["loss"])):
        fail(f"mamba2-130m losses {hist['loss']}")
    steady = hist["wall_s"][1:]
    log(f"mamba2-130m: {secs:.1f} s in all, first round {hist['wall_s'][0]:.2f} s "
        f"(incl. compile), steady {len(steady) / sum(steady):.3f} rounds/s "
        f"(smoke reading), losses {[round(x, 4) for x in hist['loss']]}")


# --------------------------------------------------------------- four chips

def phase_four_chips() -> None:
    cell = get_scenario("femnist1-fedavg-aocs-shard")
    mesh = build_client_mesh(cell.fl)
    if mesh.devices.size != 4:
        fail(f"client mesh spans {mesh.devices.size} devices, want 4")
    # The local update's f32 dots run at the TPU's default precision (bf16
    # inputs), where the mesh's and one device's different accumulation
    # orders flip bf16 roundings and the params part by ~1e-3 within 8
    # rounds. At full f32 precision only the summation order differs, which
    # is what this comparison is about.
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        p_mesh, led_mesh = run_scenario(
            cell, mode="prefetch", rounds=SIM_ROUNDS, mesh=mesh,
            artifact=os.path.join(OUT, f"{cell.name}-prefetch-mesh4.json"),
        )
        log(f"{cell.name} on a 4-chip mesh: {time.perf_counter() - t0:.1f} s")
        one = cell.with_(sharded=False)
        t0 = time.perf_counter()
        p_one, led_one = run_scenario(
            one, mode="prefetch", rounds=SIM_ROUNDS,
            artifact=os.path.join(OUT, f"{cell.name}-prefetch-1dev.json"),
        )
        log(f"{cell.name} on one device: {time.perf_counter() - t0:.1f} s")
    _ledger_line(f"sim {cell.name} prefetch mesh=4", led_mesh, 1)
    _ledger_line(f"sim {cell.name} prefetch 1 device", led_one, 1)
    m4, m1 = np.asarray(led_mesh.masks, bool), np.asarray(led_one.masks, bool)
    if not np.array_equal(m4[0], m1[0]):
        fail("round-0 mask of the 4-chip mesh differs from one device's")
    agree = sum(np.array_equal(a, b) for a, b in zip(m4, m1))
    log(f"mesh vs one device: round-0 mask bitwise equal; masks agree in "
        f"{agree}/{SIM_ROUNDS} rounds")
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(p_mesh), jax.tree_util.tree_leaves(p_one)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        if not np.allclose(a, b, rtol=1e-5, atol=1e-5):
            fail(f"final params not allclose: max |diff| {np.max(np.abs(a - b)):.3g}")
        worst = max(worst, float(np.max(np.abs(a - b))))
    log(f"final params allclose (rtol=atol=1e-5, f32 dots), max |diff| {worst:.3g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded-round phase")
    args = ap.parse_args(argv)
    device = device_check(4 if args.four_chips else 1)
    log(f"compile cache: {use_compile_cache()}")
    os.makedirs(OUT, exist_ok=True)
    phases = ([("four_chips", phase_four_chips)] if args.four_chips else [
        ("kernels", phase_kernels),
        ("sim", phase_sim),
        ("mamba2-130m", phase_mamba),
    ])
    for name, fn in phases:
        t0 = time.perf_counter()
        fn()
        log(f"phase {name} done in {time.perf_counter() - t0:.1f} s, "
            f"peak_bytes_in_use {peak_bytes()}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
