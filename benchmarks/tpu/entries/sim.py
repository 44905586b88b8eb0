"""Entry ``sim``: drive ``repro.sim.driver.run_simulation``, as a user runs
a sweep cell.

The client model is the program's own, named by the configuration
(``program_model``, a ``module:builder`` of ``src/repro`` called with the
configuration's ``program_model_args``), so this entry knows no model.

Set-up (counted in ``setup_s``): the configuration's pool and weights from
the seed, then the entry called from the seed for the checked rounds (in
scan mode one block), which compiles every program of the cell (the driver
rebuilds its jitted steps in every call, so later calls load them from the
persistent cache).  The window is ONE call of the
entry from the same seed, of the cell's nominal rate (``cells/<cell>.json``)
times ``--seconds`` rounds, so every run does the same work and lasts about
``--seconds``; ``rounds_per_s`` is its rounds over the host time from
entering the call to its return, which includes what the sim driver does inside
every call (pool upload, tracing, ledger assembly).  In scan mode every
call's round count is a multiple of ``rounds_per_scan``, so no shorter last
block compiles in the window.

The comparison: the window's own ledger (the loss, the client norms and the
participation masks of its first rounds) against the plain reference from
the same seed, and the params the entry returned after the checked rounds
(the same computation as the window's first rounds: one seed, one program)
against the reference's.
"""

from __future__ import annotations

import functools
import importlib
import time

import numpy as np

from tpubench import costs, fl_ref


def _same_tree(a, b) -> None:
    import jax

    sa = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), a)
    sb = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), b)
    if sa != sb:
        raise SystemExit(f"the benchmark's weights {sb} do not match the program's {sa}")


def _round_up(n: float, unit: int) -> int:
    return max(unit, int(-(-n // unit)) * unit)


def program_model(cfg: dict) -> tuple:
    """The program's ``(init, loss, accuracy)`` the configuration names."""
    module, builder = cfg["program_model"].split(":")
    build = getattr(importlib.import_module(module), builder)
    return build(**{k: cfg[k] for k in cfg["program_model_args"]})


def setup(cell, seed: int, tiny: bool = False) -> dict:
    import jax
    from repro.configs.base import FLConfig
    from repro.sim.driver import build_client_mesh, run_simulation

    cm, tr = cell.config_mod, cell.traffic
    cfg = cm.tiny(cell.config) if tiny else cell.config
    seed = seed % 2**31
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    pool = cm.make_pool(cfg, seed)
    init = cm.make_init(cfg)
    prog_init, loss_fn, _ = program_model(cfg)
    key = jax.random.PRNGKey(seed)
    _same_tree(jax.eval_shape(prog_init, key), jax.eval_shape(init, key))
    fl = FLConfig(**cm.fl_kwargs(cfg))
    mesh = None
    if tr["mesh"]:
        mesh = build_client_mesh(fl, devices=cell.chips)
        if mesh.devices.size != cell.chips:
            raise SystemExit(f"client mesh spans {mesh.devices.size} devices, "
                             f"the cell asks for {cell.chips}")
    unit = tr["rounds_per_scan"]
    call = functools.partial(
        run_simulation, pool, init, loss_fn, fl, batch_size=cfg["batch_size"],
        mode=tr["mode"], rounds_per_scan=unit, seed=seed,
        local_epoch=cfg["local_epoch"], mesh=mesh)

    params0 = init(jax.random.fold_in(key, fl_ref.PARAMS_FOLD))
    # this call compiles every program of the cell
    p_check, _ = call(_round_up(tr["check_rounds"], unit))
    program = {"change": fl_ref.leaf_norms(fl_ref.tree_diff(params0, p_check))}
    del p_check, params0
    return {"cfg": cfg, "cm": cm, "traffic": tr, "seed": seed, "pool": pool, "call": call,
            "unit": unit, "shards": 1 if mesh is None else cell.chips,
            "nominal": cell.spec["nominal_rounds_per_s"], "program": program}


def rounds_for(state: dict, seconds: float) -> int:
    """The window's rounds: the cell's nominal rate times ``seconds``, a
    whole number of scan blocks, never fewer than the checked rounds — the
    same work in every run."""
    n = max(round(state["nominal"] * seconds), state["traffic"]["check_rounds"])
    return _round_up(n, state["unit"])


def window(state: dict, seconds: float) -> dict:
    """One call of the entry, sized to last about ``seconds``."""
    import jax

    n = rounds_for(state, seconds)
    t0 = time.perf_counter()
    params, led = state["call"](n)
    jax.block_until_ready(params)
    secs = time.perf_counter() - t0
    cfg, r = state["cfg"], state["traffic"]["check_rounds"]
    state["program"].update(losses=led.loss[:r], norms=np.asarray(led.norms[:r]),
                            masks=np.asarray(led.masks[:r]))
    losses = np.asarray(led.loss, np.float64)
    d, itemsize = cfg["params"], state["cm"].aggregate_itemsize(cfg)
    # one masked-aggregate call per round on each shard: every shard writes
    # the (d,) partial, the round's sent rows are read once in all
    agg_bytes = sum(costs.aggregate_bytes(d, s, itemsize) + (state["shards"] - 1) * 4.0 * d
                    for s in led.sent)
    return {"rounds": n, "seconds": secs, "attempted": n,
            "failed": int(np.sum(~np.isfinite(losses))),
            "context": {"aggregate_bytes": agg_bytes,
                        "flops_per_round": state["cm"].flops_per_round(cfg)}}


def release(state: dict) -> None:
    """Drop the program's state before the reference runs."""
    import gc

    import jax

    state.pop("call", None)
    gc.collect()
    jax.clear_caches()


def reference(state: dict, mode: str = "highest") -> dict:
    return state["cm"].reference(state["cfg"], state["pool"], state["seed"],
                                 state["traffic"]["check_rounds"], mode)


def compile_for_tpu(cell) -> list:
    """The cell's round step at its real size, compiled for a described v5e
    (``tpubench.described``): on one chip, or on the cell's client mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding
    from repro.configs.base import FLConfig
    from repro.fl.engine import RoundEngine, make_engine

    from tpubench import described

    cfg, cm = cell.config, cell.config_mod
    topo = described.topology()
    _, loss_fn, _ = program_model(cfg)
    fl = FLConfig(**cm.fl_kwargs(cfg))
    n, r, b = cfg["n_clients"], cfg["local_steps"], cfg["batch_size"]
    params = jax.eval_shape(cm.make_init(cfg), jax.random.PRNGKey(0))
    sds = jax.ShapeDtypeStruct
    # one example's keys, shapes and dtypes, from a tiny pool of the configuration
    example = cm.make_pool(cm.tiny(cfg), 0).client_data[0]
    batch = {k: sds((n, r, b) + v.shape[1:], v.dtype) for k, v in example.items()}
    batch["_step_mask"] = sds((n, r), jnp.float32)
    weights, key = sds((n,), jnp.float32), sds((2,), jnp.uint32)
    if cell.traffic["mesh"]:
        mesh = Mesh(np.array(topo.devices[:cell.chips]), (fl.client_axis,))
        step = make_engine(loss_fn, fl, mesh=mesh, interpret=False)
        rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P(fl.client_axis))
        args = (described.placed(params, rep), (), described.placed(batch, shard),
                described.placed(weights, shard), described.placed(key, rep))
    else:
        one = SingleDeviceSharding(topo.devices[0])
        step = RoundEngine(loss_fn, fl, interpret=False).make_step()
        args = (described.placed(params, one), (), described.placed(batch, one),
                described.placed(weights, one), described.placed(key, one))
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        compiled = jax.jit(step).lower(*args, None, None).compile()
    return [described.report(compiled, cell.chips)]
