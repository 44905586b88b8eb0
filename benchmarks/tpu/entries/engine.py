"""Entry ``engine``: drive the jitted round step of ``repro.fl.engine.make_engine``
for a model of the program's registry, as the ``launch/train.py`` arch loop
builds it.

Set-up (counted in ``setup_s``): the weights and a set of distinct token
batches and round keys made on the device from the seed; the step jitted;
then the window's own loop drives it from the seed through the checked
rounds (the first call compiles).  The same step and its params go on into
the window, which dispatches the cell's nominal rate (``cells/<cell>.json``)
times ``--seconds`` rounds — at most ``in_flight`` not yet finished — then
waits for the last; ``rounds_per_s`` is the rounds over that time.

The comparison: the checked rounds' losses, client norms and masks, the
params' change after the first round and after the checked rounds, each
against the plain reference (``configs/<config>.py``) on the same weights,
batches and keys.
"""

from __future__ import annotations

import collections
import time

from tpubench import costs, fl_ref


def setup(cell, seed: int, tiny: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get
    from repro.configs.base import FLConfig
    from repro.fl.engine import make_engine
    from repro.models import build_model

    cm, tr = cell.config_mod, cell.traffic
    cfg = cm.tiny(cell.config) if tiny else cell.config
    seed = seed % 2**31
    mc = get(cfg["program_model"])
    if tiny:
        mc = mc.reduced()
    want = cm.program_fields(cfg)
    have = {k: getattr(mc, k) for k in want}
    if have != want:
        raise SystemExit(f"the program's {mc.name} is {have}, the configuration {want}")
    model = build_model(mc, remat=cfg["remat"])
    fl = FLConfig(**cm.fl_kwargs(cfg))
    step = jax.jit(make_engine(model.loss, fl))

    key = jax.random.PRNGKey(seed)
    init = cm.make_init(cfg)
    params = init(jax.random.fold_in(key, fl_ref.PARAMS_FOLD))
    shapes = jax.eval_shape(model.init, key)
    mine = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    if jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), shapes) != mine:
        raise SystemExit("the benchmark's weights do not match the program's layout")
    batches, keys = cm.make_inputs(cfg, seed, tr["batches"])
    weights = jnp.full((fl.n_clients,), 1.0 / fl.n_clients, jnp.float32)

    params0 = params
    prog = {"losses": [], "norms": [], "masks": []}
    for k in range(tr["check_rounds"]):
        params, _, m = step(params, (), batches[k], weights, keys[k], None, None)
        prog["losses"].append(float(m.loss))
        prog["norms"].append(jax.device_get(m.norms))
        prog["masks"].append(jax.device_get(m.mask))
        if k == 0:
            prog["first"] = fl_ref.leaf_norms(fl_ref.tree_diff(params0, params))
    prog["change"] = fl_ref.leaf_norms(fl_ref.tree_diff(params0, params))
    del params0
    return {"cfg": cfg, "cm": cm, "traffic": tr, "seed": seed, "step": step,
            "params": params, "round": tr["check_rounds"], "batches": batches,
            "keys": keys, "weights": weights, "program": prog,
            "nominal": cell.spec["nominal_rounds_per_s"]}


def window(state: dict, seconds: float) -> dict:
    import jax
    import numpy as np

    step, batches, keys, w = state["step"], state["batches"], state["keys"], state["weights"]
    count, depth = len(batches), state["traffic"]["in_flight"]
    params, k0 = state["params"], state["round"]
    rounds = max(1, round(state["nominal"] * seconds))
    pending, losses = collections.deque(), []
    t0 = time.perf_counter()
    for k in range(k0, k0 + rounds):
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            params, _, m = step(params, (), batches[k % count], w, keys[k % count],
                                None, None)
        pending.append(m.loss)
        losses.append(m.loss)
        if len(pending) > depth:
            with jax.profiler.TraceAnnotation("bench:wait"):
                pending.popleft().block_until_ready()
    with jax.profiler.TraceAnnotation("bench:wait"):
        jax.block_until_ready(params)
    secs = time.perf_counter() - t0
    state["params"], state["round"] = params, k0 + rounds
    losses = np.asarray(jax.device_get(losses), np.float64)
    cfg = state["cfg"]
    groups = cfg["n_clients"] // cfg["scan_group"]
    # per round: each scan group's (scan_group, D) update matrix streamed
    # once by the fused norm+aggregate kernel, which reads all its rows
    per_round = groups * costs.aggregate_bytes(
        cfg["params"], cfg["scan_group"], state["cm"].aggregate_itemsize(cfg))
    return {"rounds": rounds, "seconds": secs, "attempted": rounds,
            "failed": int(np.sum(~np.isfinite(losses))),
            "context": {"aggregate_bytes": per_round * rounds,
                        "flops_per_round": state["cm"].flops_per_round(cfg)}}


def release(state: dict) -> None:
    import gc

    import jax

    for k in ("step", "params"):
        state.pop(k, None)
    gc.collect()
    jax.clear_caches()


def reference(state: dict, mode: str = "highest") -> dict:
    import jax

    cfg, cm = state["cfg"], state["cm"]
    key = jax.random.PRNGKey(state["seed"])
    params0 = cm.make_init(cfg)(jax.random.fold_in(key, fl_ref.PARAMS_FOLD))
    return cm.reference(cfg, params0, state["batches"], state["keys"],
                        state["traffic"]["check_rounds"], mode)


def compile_for_tpu(cell) -> list:
    """The cell's round step at its real size, compiled for one described
    v5e chip (``tpubench.described``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.configs import get
    from repro.configs.base import FLConfig
    from repro.fl.engine import RoundEngine
    from repro.models import build_model

    from tpubench import described

    cfg, cm = cell.config, cell.config_mod
    one = SingleDeviceSharding(described.topology().devices[0])
    model = build_model(get(cfg["program_model"]), remat=cfg["remat"])
    step = RoundEngine(model.loss, FLConfig(**cm.fl_kwargs(cfg)), interpret=False).make_step()
    sds = jax.ShapeDtypeStruct
    shape = (cfg["n_clients"], cfg["local_steps"], cfg["batch"], cfg["seq_len"])
    batch = {"tokens": sds(shape, jnp.int32), "targets": sds(shape, jnp.int32)}
    args = (described.placed(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one), (),
            described.placed(batch, one),
            described.placed(sds((cfg["n_clients"],), jnp.float32), one),
            described.placed(sds((2,), jnp.uint32), one))
    compiled = jax.jit(step).lower(*args, None, None).compile()
    return [described.report(compiled, 1)]
