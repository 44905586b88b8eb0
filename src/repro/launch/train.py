"""Training driver: federated training of any assigned architecture (reduced
or full) with OCS, on the local device set or a forced-host-device mesh —
or a registered simulation scenario through the cohort-streaming sim driver.

Engine selection is mesh-aware (fl.engine.make_engine): with more than one
device (or ``--shard on``) the client dimension shards over a 1-D ``data``
mesh and the round runs through fl/shard_round.py's explicit collectives —
``--agg-backend pallas`` then aggregates via the per-shard fused kernel plus
one cross-shard psum (kernels/sharded_aggregate.py).

``--scenario NAME`` instead runs one cell of the paper's experiment grid
(repro/sim/scenarios.py) through ``repro.sim.driver``: ``--prefetch``
selects the double-buffered device-pool pipeline vs the legacy host loop,
``--sim-rounds-per-scan N`` (N > 0) the scan-over-rounds fast path, and
``--shard on`` runs the cell on a client mesh (shard_map round + sharded
``ClientPool``; ``Scenario.sharded`` cells build that mesh automatically —
scan-over-rounds and a mesh are mutually exclusive).  The ledger artifact
lands under benchmarks/artifacts/sim/.

``--sampler NAME`` picks the client-selection rule from the sampler zoo
(``core/sampling.py::SAMPLERS`` — optimal / aocs / uniform / full /
clustered / cyclic / threshold) on either branch: it sets the arch
workload's ``FLConfig.sampler``, or overrides a scenario cell's own rule.
Stateful samplers (cyclic/threshold) have their ``SamplerState`` carried
round to round on both paths.

``--stragglers SPEC`` / ``--deadline T`` switch on the client-state layer
(repro/sim/pool.py): Markov availability chains, heterogeneous latency vs a
round deadline, dropout fault injection, with ``over=`` over-selection.
They compose with both branches — overriding a scenario cell's own
``SystemConfig``, or threading an availability trace through the arch
round loop (e.g. ``--stragglers p_up=0.35,p_down=0.15,drop=0.1,over=2
--deadline 2.0``).

``--metrics-port`` / ``--diag-every`` / ``--obs-jsonl`` / ``--trace-dir``
switch on the observability layer (repro/obs, docs/observability.md) on
either branch: a live JSON/Prometheus endpoint, the online Eq. 2 gap
estimator (``‖ŝ − s‖²`` vs the full-participation aggregate, single-device
only), a schema-versioned JSONL event stream, and a
``jax.profiler.start_trace`` window over the first ``--trace-rounds``
rounds for TensorBoard/Perfetto.

``--checkpoint DIR`` / ``--ckpt-every N`` / ``--resume PATH`` checkpoint
and resume on either branch (docs/architecture.md#checkpoint--resume).
With ``--scenario`` they thread the sim driver's full-fidelity
``RoundCheckpoint`` layer: a resumed run finishes with bitwise-identical
params and a byte-identical ledger (minus wall-clock) vs the uninterrupted
one.  On the arch branch the checkpoint carries the FULL training state —
params, the ``--server-opt`` state, the synthetic-batch RNG bit-state, the
client-state chains and the sampler carry — an earlier version saved
params only, so a "restored" momentum/Adam run silently diverged from its
own continuation.  Both branches refuse a checkpoint whose config
fingerprint differs from the invocation's flags.

Examples (CPU container — reduced configs):
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b-reduced \\
      --rounds 20 --clients 8 --expected 2 --sampler aocs
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b-reduced \\
      --clients 8 --shard on --agg-backend pallas
  PYTHONPATH=src python -m repro.launch.train --scenario list
  PYTHONPATH=src python -m repro.launch.train \\
      --scenario femnist1-fedavg-aocs --reduced --sim-rounds-per-scan 8
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (
    CheckpointConfig,
    read_meta,
    restore,
    save,
)
from repro.checkpoint.resume import config_diff, fingerprint
from repro.configs import get
from repro.configs.base import FLConfig
from repro.fl.engine import make_engine
from repro.fl.round import client_weights, round_bits
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.obs.trace import span as obs_span


def synthetic_token_batch(rng, cfg, n, r, b, s):
    batch = {
        "tokens": rng.integers(0, cfg.vocab_size, size=(n, r, b, s)).astype(np.int32),
    }
    batch["targets"] = batch["tokens"]
    if cfg.encoder_seq:
        batch["frames"] = rng.normal(size=(n, r, b, cfg.encoder_seq, cfg.d_model)).astype(
            np.float32
        ) * 0.02
    if cfg.prefix_tokens:
        batch["patches"] = rng.normal(
            size=(n, r, b, cfg.prefix_tokens, cfg.d_model)
        ).astype(np.float32) * 0.02
    return {k: jnp.asarray(v) for k, v in batch.items()}


def parse_stragglers(spec: str | None, deadline: float | None):
    """``--stragglers``/``--deadline`` -> ``(SystemConfig | None, over_select)``.

    ``spec`` is a comma-separated k=v list over the client-state knobs —
    ``p_up``, ``p_down``, ``latency_mu``, ``latency_sigma``, ``drop``
    (drop_prob) and ``over`` (FLConfig.over_select) — e.g.
    ``p_up=0.35,p_down=0.15,drop=0.1,over=2``; ``deadline`` is its own flag
    (it composes with the defaults when given alone).  Returns
    ``(None, None)`` when neither flag was passed.
    """
    if spec is None and deadline is None:
        return None, None
    from repro.sim.pool import SystemConfig

    kw, over = {}, None
    for part in (spec.split(",") if spec else []):
        if "=" not in part:
            raise SystemExit(f"--stragglers entry {part!r} is not k=v")
        k, v = part.split("=", 1)
        k = k.strip()
        try:
            v = float(v)
        except ValueError:
            raise SystemExit(f"--stragglers {k}={v!r}: not a number") from None
        if k == "over":
            over = v
        elif k == "drop":
            kw["drop_prob"] = v
        elif k in ("p_up", "p_down", "latency_mu", "latency_sigma"):
            kw[k] = v
        else:
            raise SystemExit(
                f"--stragglers key {k!r} unknown; want p_up, p_down, "
                f"latency_mu, latency_sigma, drop, over"
            )
    if deadline is not None:
        kw["deadline"] = deadline
    try:
        return SystemConfig(**kw), over
    except ValueError as e:
        raise SystemExit(f"--stragglers/--deadline: {e}") from None


def obs_from_args(args, mode: str | None = None):
    """``--metrics-port``/``--diag-every``/... -> ObsConfig | None.

    Returns None when no obs flag was passed, so both branches keep the
    exact telemetry-off code path by default.  ``--obs-phases auto``
    enables phased execution only where it applies (host mode).
    """
    if (args.metrics_port is None and args.diag_every == 0
            and args.obs_jsonl is None and args.trace_dir is None
            and args.obs_phases != "on"):
        return None
    from repro.obs import ObsConfig

    phases = args.obs_phases == "on" or (
        args.obs_phases == "auto" and mode == "host"
    )
    return ObsConfig(
        diag_every=args.diag_every, metrics_port=args.metrics_port,
        jsonl=args.obs_jsonl, trace_dir=args.trace_dir,
        trace_rounds=args.trace_rounds, phases=phases,
    )


def run_scenario_cli(args):
    """The ``--scenario`` branch: one experiment-grid cell via repro.sim."""
    from repro.sim.driver import build_client_mesh, run_scenario
    from repro.sim.scenarios import get_scenario, list_scenarios

    if args.scenario == "list":
        for name in list_scenarios():
            sc = get_scenario(name)
            shard = " [sharded]" if sc.sharded else ""
            print(f"{name:40s} {sc.paper}{shard}")
        return
    if args.sim_rounds_per_scan > 0:
        mode = "scan"
    else:
        mode = "prefetch" if args.prefetch == "on" else "host"
    sc = get_scenario(args.scenario)
    if args.sampler:
        # --sampler overrides the cell's own rule (validated up front by the
        # engine factories via sampling.resolve_sampler)
        sc = sc.with_(fl=dataclasses.replace(sc.fl, sampler=args.sampler))
    system, over = parse_stragglers(args.stragglers, args.deadline)
    if system is not None:
        # CLI overrides the cell's own system config (if any); 'over=' rides
        # into the FLConfig so the plan actually over-selects.
        fl = sc.fl if over is None else dataclasses.replace(sc.fl, over_select=over)
        sc = sc.with_(system=system, fl=fl)
    if args.shard == "off":
        # an explicit off overrides even a Scenario.sharded cell (the only
        # way to run a mesh cell's config single-device / in scan mode)
        sc = sc.with_(sharded=False)
    effective = sc.reduced() if args.reduced else sc
    mesh = None
    if args.shard == "on" or effective.sharded:
        if mode == "scan":
            raise SystemExit(
                "--sim-rounds-per-scan and a mesh conflict: the shard_map "
                "round cannot run inside the scan-over-rounds block "
                "(docs/architecture.md#limits) — drop --sim-rounds-per-scan "
                "or pass --shard off"
            )
        mesh = build_client_mesh(effective.fl)
    # the artifact path carries the effective (possibly -reduced) name, so a
    # reduced smoke never clobbers a full run's ledger
    artifact = os.path.join(
        "benchmarks", "artifacts", "sim", f"{effective.name}-{mode}.json"
    )
    shards = 0 if mesh is None else mesh.devices.shape[0]
    print(f"[sim] scenario {effective.name} ({sc.paper}) mode={mode}"
          f"{f' mesh={shards}' if shards else ''} "
          f"rounds={args.rounds if args.rounds is not None else effective.rounds}")
    obs = obs_from_args(args, mode=mode)
    if obs is not None and obs.diag_every > 0 and mesh is not None:
        raise SystemExit(
            "--diag-every and a mesh conflict: the obs gap estimator is "
            "single-device only (docs/architecture.md#limits) — drop "
            "--diag-every or pass --shard off"
        )
    ckpt_cfg = None
    if args.checkpoint:
        ckpt_cfg = CheckpointConfig(args.checkpoint, every=args.ckpt_every)
    _, ledger = run_scenario(
        sc, reduced=args.reduced, mode=mode, rounds=args.rounds,
        rounds_per_scan=max(args.sim_rounds_per_scan, 1), mesh=mesh,
        artifact=artifact, obs=obs, checkpoint=ckpt_cfg, resume=args.resume,
    )
    if ckpt_cfg is not None:
        print(f"[sim] round checkpoints under {ckpt_cfg.dir} "
              f"(every {ckpt_cfg.every})")
    for k, (loss, sent) in enumerate(zip(ledger.loss, ledger.sent)):
        sys_col = ""
        if effective.system is not None:
            sys_col = (f"sel {ledger.over_selected[k]} "
                       f"miss {ledger.deadline_misses[k]} "
                       f"drop {ledger.dropouts[k]} ")
        print(f"[round {k:3d}] loss {loss:.4f} alpha {ledger.alpha[k]:.3f} "
              f"sent {sent}/{ledger.fl['n_clients']} {sys_col}"
              f"up {ledger.uplink_bits[k]/1e9:.2f}G down {ledger.downlink_bits[k]/1e9:.2f}G")
    if ledger.gap_rounds:
        gaps = ", ".join(
            f"r{r}={g:.3g}"
            for r, g in zip(ledger.gap_rounds, ledger.gap_ratio)
        )
        print(f"[sim] Eq. 2 gap ratio on the diag grid: {gaps}")
    print(f"[sim] {ledger.rounds_per_sec:.1f} rounds/s (steady-state), "
          f"artifact {artifact}")


def main(argv=None):
    """Parse ``argv`` and run one branch.  The arch branch returns its
    per-round ``{"loss": [...], "wall_s": [...]}``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="assigned architecture to train (omit with --scenario)")
    ap.add_argument("--rounds", type=int, default=None,
                    help="communication rounds (default: 10, or the "
                         "scenario's own rounds with --scenario)")
    ap.add_argument("--scenario", default=None,
                    help="run a registered sim scenario instead of an arch "
                         "workload ('list' prints the registry)")
    ap.add_argument("--reduced", action="store_true",
                    help="with --scenario: the seconds-scale reduced variant")
    ap.add_argument("--prefetch", default="on", choices=["on", "off"],
                    help="with --scenario: double-buffered device-pool "
                         "pipeline (on) vs legacy host loop (off)")
    ap.add_argument("--sim-rounds-per-scan", type=int, default=0,
                    help="with --scenario: >0 selects the scan-over-rounds "
                         "fast path with this block length")
    ap.add_argument("--stragglers", default=None, metavar="SPEC",
                    help="client-state layer spec, comma-separated k=v over "
                         "p_up, p_down, latency_mu, latency_sigma, drop "
                         "(drop_prob), over (over_select) — e.g. "
                         "'p_up=0.35,p_down=0.15,drop=0.1,over=2'")
    ap.add_argument("--deadline", type=float, default=None,
                    help="round deadline in latency units (enables the "
                         "client-state layer; composes with --stragglers)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve a live JSON/Prometheus metrics endpoint on "
                         "this port (0 = ephemeral; repro/obs/http.py)")
    ap.add_argument("--diag-every", type=int, default=0,
                    help="run the online Eq. 2 gap estimator every N rounds "
                         "(0 = off; single-device only)")
    ap.add_argument("--obs-jsonl", default=None, metavar="PATH",
                    help="append the schema-versioned obs event stream "
                         "(JSONL, one event per line) to PATH")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="profile the first --trace-rounds rounds with "
                         "jax.profiler.start_trace into DIR "
                         "(TensorBoard/Perfetto)")
    ap.add_argument("--trace-rounds", type=int, default=3,
                    help="rounds covered by the --trace-dir profiler window")
    ap.add_argument("--obs-phases", default="auto",
                    choices=["auto", "on", "off"],
                    help="phased round execution for real per-phase spans "
                         "(auto: on whenever any obs flag is set; host-mode "
                         "vmap engines only — see docs/observability.md)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--expected", type=int, default=2)
    ap.add_argument("--sampler", default=None,
                    choices=["optimal", "aocs", "uniform", "full",
                             "clustered", "cyclic", "threshold"],
                    help="client-selection rule (sampler zoo; default: aocs "
                         "on the arch path, the scenario's own sampler with "
                         "--scenario)")
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr-local", type=float, default=0.05)
    ap.add_argument("--server-opt", default="none",
                    choices=["none", "momentum", "adam"],
                    help="server-side optimizer applied to the aggregated "
                         "update (arch branch; its state rides in "
                         "--checkpoint, so a resumed run continues the same "
                         "trajectory)")
    ap.add_argument("--lr-server", type=float, default=1.0,
                    help="server optimizer learning rate (--server-opt)")
    ap.add_argument("--checkpoint", default=None, metavar="DIR",
                    help="write full-state checkpoints under DIR every "
                         "--ckpt-every rounds (atomic step-XXXXXXXX dirs; "
                         "params + server-opt state + RNG bit-state + "
                         "client/sampler state)")
    ap.add_argument("--ckpt-every", type=int, default=10,
                    help="rounds between --checkpoint writes")
    ap.add_argument("--resume", default=None, metavar="PATH",
                    help="resume from a checkpoint root (latest complete "
                         "step) or a specific step-XXXXXXXX directory; "
                         "rejected if its config fingerprint differs from "
                         "this invocation's flags")
    ap.add_argument("--shard", default="auto", choices=["auto", "on", "off"],
                    help="shard clients over a 1-D data mesh (auto: when >1 "
                         "device and clients divide the device count)")
    ap.add_argument("--engine", default="vmap", choices=["vmap", "scan"])
    ap.add_argument("--agg-backend", default="jnp", choices=["jnp", "pallas"])
    ap.add_argument("--scan-group", type=int, default=2,
                    help="clients per scan group (--engine scan)")
    ap.add_argument("--cache-groups", type=int, default=8,
                    help="bounded HBM update cache: groups whose pass-1 "
                         "update matrices are kept so the post-plan aggregate "
                         "needs no recompute (0 = two-pass recompute; "
                         ">= clients/scan-group = single-pass)")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.scenario:
        return run_scenario_cli(args)
    if args.arch is None:
        ap.error("one of --arch or --scenario is required")
    if args.rounds is None:
        args.rounds = 10

    # the run's set-up, named in any profiler trace as the sim driver's
    # is: model, engine, params, client-state and sampler init
    with obs_span("setup"):
        cfg = get(args.arch)
        # remat: each client's local step keeps one layer's activations, not
        # all of them — without it a mamba2-130m scan group (2 clients x 4 x
        # 512 tokens) needs more than a v5e's 16 GB of HBM
        model = build_model(cfg)
        system, over = parse_stragglers(args.stragglers, args.deadline)
        server_opt = None
        if args.server_opt == "momentum":
            from repro.optim import sgd

            server_opt = sgd(args.lr_server, momentum=0.9)
        elif args.server_opt == "adam":
            from repro.optim import adam

            server_opt = adam(args.lr_server)
        fl = FLConfig(
            n_clients=args.clients, expected_clients=args.expected,
            sampler=args.sampler or "aocs",
            local_steps=args.local_steps, lr_local=args.lr_local,
            round_engine=args.engine, agg_backend=args.agg_backend,
            scan_group=args.scan_group, cache_groups=args.cache_groups,
            over_select=over if over is not None else 1.0,
        )
        key = jax.random.PRNGKey(0)
        params = model.init(key)
        dim = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
        opt_state = server_opt.init(params) if server_opt is not None else ()
        state = state_step = None
        if system is not None:
            # arch path: every round's cohort IS the full client set, so the
            # trace covers all n clients each round.
            from repro.sim.pool import init_client_state, step_client_state

            state = init_client_state(fl.n_clients, system, jax.random.fold_in(key, 2))
            state_step = jax.jit(
                lambda st, kk, c: step_client_state(st, kk, c, system)
            )

        n_dev = jax.device_count()
        # the shard_map round has no scan/cache memory policy (see
        # docs/architecture.md#limits): an explicit scan request conflicts with
        # --shard on, and wins over --shard auto (never silently dropped).
        if args.shard == "on" and args.engine == "scan":
            raise SystemExit(
                "--shard on and --engine scan conflict: the shard_map round has "
                "no scan/cache memory policy (docs/architecture.md#limits) — "
                "drop one of the two flags"
            )
        shard = args.shard == "on" or (
            args.shard == "auto" and n_dev > 1 and fl.n_clients % n_dev == 0
            and args.engine != "scan"
        )
        mesh = None
        if shard:
            if fl.n_clients % n_dev:
                raise SystemExit(
                    f"--shard on needs n_clients ({fl.n_clients}) divisible by the "
                    f"device count ({n_dev})"
                )
            mesh = jax.make_mesh((n_dev,), (fl.client_axis,))
        engine = f"shard_map/{n_dev}" if shard else fl.round_engine
        print(f"[train] {cfg.name}: {dim/1e6:.1f}M params, n={fl.n_clients} "
              f"m={fl.expected_clients} sampler={fl.sampler} engine={engine} "
              f"agg={fl.agg_backend}")
        # obs layer: the arch loop is synchronous (a host loop), so phase spans
        # and the gap estimator apply exactly as in the sim driver's host mode.
        obs = obs_from_args(args, mode="host")
        tel = None
        if obs is not None:
            from repro.obs import Telemetry

            tel = Telemetry(obs)
        history = {"loss": [], "wall_s": []}
        diag_on = tel is not None and tel.cfg.diag_every > 0
        if diag_on and mesh is not None:
            raise SystemExit(
                "--diag-every and a mesh conflict: the obs gap estimator is "
                "single-device only (docs/architecture.md#limits) — drop "
                "--diag-every or pass --shard off"
            )
        phased_step = step_diag = None
        if mesh is None:
            from repro.fl.engine import RoundEngine

            eng = RoundEngine(model.loss, fl, server_opt)
            if tel is not None and tel.cfg.phases and eng.memory == "vmap":
                from repro.obs.phased import make_phased_step

                phased_step = make_phased_step(eng, tel)
            else:
                step = jax.jit(eng.make_step())
                if diag_on:
                    step_diag = jax.jit(eng.make_step(True))
        else:
            if server_opt is not None:
                raise SystemExit(
                    "--server-opt and a mesh conflict: the shard_map round has "
                    "no server-optimizer stage (docs/architecture.md#limits) — "
                    "drop --server-opt or pass --shard off"
                )
            step = jax.jit(make_engine(model.loss, fl, mesh=mesh))
        w = client_weights(fl)
        rng = np.random.default_rng(0)
        total_bits = 0
        # stateful samplers (cyclic/threshold): carry their SamplerState round
        # to round, exactly like the sim driver does.
        from repro.core.sampling import init_sampler_state, is_stateful

        samp = init_sampler_state() if is_stateful(fl.sampler) else None

    # full-state checkpoint/resume: the arch trajectory is defined by
    # (params, server-opt state, the synthetic-batch RNG stream, the
    # client-state chains, the sampler carry) — ALL of it rides in the
    # checkpoint, fingerprinted over the flags that shape the run.  An
    # earlier version saved params only, so a restored momentum/Adam run
    # silently diverged from its own continuation.
    ckpt_doc = {
        "arch": cfg.name,
        "fl": dataclasses.asdict(fl),
        "system": None if system is None else dataclasses.asdict(system),
        "batch": args.batch, "seq": args.seq,
        "server_opt": args.server_opt, "lr_server": args.lr_server,
    }

    def arch_tree():
        return {
            "params": params, "opt_state": opt_state,
            "client_state": state if state is not None else (),
            "sampler_state": samp if samp is not None else (),
        }

    k0 = 0
    if args.resume:
        meta, _ = read_meta(args.resume)
        if meta.get("arch_fingerprint") != fingerprint(ckpt_doc):
            diffs = "; ".join(config_diff(meta.get("config", {}), ckpt_doc))
            raise SystemExit(
                "--resume: checkpoint/flag fingerprint mismatch — resuming "
                "would silently change the trajectory. Differing keys: "
                + (diffs or "<fingerprint only>")
            )
        tree, _ = restore(args.resume, arch_tree())
        params, opt_state = tree["params"], tree["opt_state"]
        if state is not None:
            state = tree["client_state"]
        if samp is not None:
            samp = tree["sampler_state"]
        rng.bit_generator.state = meta["rng_state"]
        total_bits = int(meta["total_bits"])
        k0 = int(meta["round"])
        if k0 >= args.rounds:
            raise SystemExit(
                f"--resume: checkpoint already covers round {k0} — raise "
                f"--rounds past it to extend the run"
            )
        print(f"[train] resumed at round {k0} from {args.resume}")

    def write_ckpt(k_done):
        d = save(
            args.checkpoint, jax.device_get(arch_tree()), step=k_done + 1,
            meta={
                "round": k_done + 1,
                "rng_state": copy.deepcopy(rng.bit_generator.state),
                "total_bits": int(total_bits),
                "config": ckpt_doc,
                "arch_fingerprint": fingerprint(ckpt_doc),
            },
            keep=3,
        )
        print(f"[train] checkpoint -> {d}")

    if tel is not None:
        tel.run_start(arch=cfg.name, mode="train", sampler=fl.sampler,
                      n_clients=fl.n_clients, rounds=args.rounds,
                      backend=jax.default_backend())
    for k in range(k0, args.rounds):
        if tel is not None:
            tel.round_start(k)
        # spans as in the sim driver: `data` and `round` record into the
        # telemetry when it is on (and then wait on their block target);
        # `compile` and `ledger` only annotate
        with obs_span("data", tel) as s:
            batch = synthetic_token_batch(rng, cfg, fl.n_clients, fl.local_steps,
                                          args.batch, args.seq)
            s.block(batch)
        t0 = time.perf_counter()
        kk = jax.random.fold_in(key, k)
        diag = diag_on and tel.want_gap(k)
        sys_col = ""
        if state is not None:
            state, trace = state_step(state, kk, jnp.arange(fl.n_clients))
        else:
            trace = None
        if phased_step is not None:
            params, opt_state, m = phased_step(
                params, opt_state, batch, w, kk, trace, samp, diag=diag
            )
        else:
            with obs_span("round", tel) as s:
                # the run's first dispatch traces, lowers and compiles the
                # step (or loads it from the persistent cache)
                with obs_span("compile") if k == k0 else contextlib.nullcontext():
                    params, opt_state, m = (step_diag if diag else step)(
                        params, opt_state, batch, w, kk, trace, samp
                    )
                s.block(m.loss)
        if samp is not None:
            samp = m.sampler_state
        # the round's reads of its metrics, the only syncs of the loop
        with obs_span("ledger"):
            if state is not None:
                sys_col = (f"sel {int(m.selected_clients)} "
                           f"miss {int(m.deadline_misses)} drop {int(m.dropouts)} ")
            loss = float(m.loss)
            total_bits += round_bits(fl, dim, m.mask)
            wall_s = time.perf_counter() - t0
            history["loss"].append(loss)
            history["wall_s"].append(wall_s)
            if diag:
                tel.record_gap(k, float(m.gap.gap_sq), float(m.gap.full_sq))
            if tel is not None:
                # read only with telemetry on: a client whose update norm is
                # NaN or inf would poison the whole cohort's sampling plan
                norms = np.asarray(jax.device_get(m.norms))
                tel.record_round(
                    k, loss=loss, sent_clients=int(m.sent_clients),
                    wall_ms=wall_s * 1e3, uplink_bits_total=int(total_bits),
                    nonfinite_norms=int(np.sum(~np.isfinite(norms))),
                )
            print(f"[round {k:3d}] loss {loss:.4f} alpha {float(m.alpha):.3f} "
                  f"gamma {float(m.gamma):.3f} sent {int(m.sent_clients)}/{fl.n_clients} "
                  f"{sys_col}bits {total_bits/1e9:.2f}G ({wall_s:.1f}s)")
        if args.checkpoint and (
            (k + 1) % args.ckpt_every == 0 or k + 1 == args.rounds
        ):
            write_ckpt(k)
    if tel is not None:
        tel.finish(rounds=args.rounds)
        tel.close()
    return history


if __name__ == "__main__":
    main()
