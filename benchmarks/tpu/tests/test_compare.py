"""The comparison's arithmetic, the fixed compile cache and the model a
configuration names, without running a cell."""

import numpy as np
import pytest

from fault_run import benchmark_with_dormant
from tpubench import compare, harness


def readings(scale: float = 1.0, first: bool = False) -> dict:
    out = {"losses": [2.0, 1.5, 1.25], "norms": np.array([[1.0, 2.0, 4.0]] * 3),
           "masks": np.array([[True, False, True]] * 3),
           "change": {"w": 3.0 * scale, "b": 0.5 * scale}}
    if first:
        out["first"] = {"w": 1.0 * scale, "b": 0.25 * scale}
    return out


def test_identical_readings_are_correct():
    nums = compare.numbers(readings(), readings())
    assert nums == {"loss_gap": 0.0, "norm_gap": 0.0, "mask_mismatch": 0.0, "update_gap": 0.0}
    ok, rows = compare.judge(nums, dict.fromkeys(nums, 0.0))
    assert ok and [r[0] for r in rows] == list(nums)


@pytest.mark.parametrize("first", [False, True])
def test_first_update_compared_only_where_read(first):
    nums = compare.numbers(readings(first=first), readings(first=first))
    assert ("agg_gap" in nums) is first


def test_a_changed_number_fails_its_limit():
    prog = readings(scale=1.01)
    nums = compare.numbers(prog, readings())
    assert nums["update_gap"] == pytest.approx(0.01)
    ok, _ = compare.judge(nums, {"loss_gap": 0.0, "norm_gap": 0.0, "mask_mismatch": 0.0,
                                 "update_gap": 1e-3})
    assert not ok


def test_nan_is_never_correct():
    ok, _ = compare.judge({"loss_gap": float("nan")}, {"loss_gap": 1.0})
    assert not ok


def test_compile_cache_stays_in_the_checkout(monkeypatch, tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        assert harness.enable_compile_cache() == str(harness.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(harness.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("workload", [w["name"] for w in benchmark_with_dormant()["workloads"]
                                      if w["config"] == "femnist_mlp"])
def test_sim_entry_builds_the_configured_model(workload):
    import jax

    cell = harness.load_cell(workload, benchmark_with_dormant())
    harness.require_program()
    cfg = cell.config_mod.tiny(cell.config)
    init, loss, _ = cell.entry.program_model(cfg)
    key = jax.random.PRNGKey(0)
    have = jax.tree_util.tree_map(lambda x: x.shape, jax.eval_shape(init, key))
    want = jax.tree_util.tree_map(lambda x: x.shape,
                                  jax.eval_shape(cell.config_mod.make_init(cfg), key))
    assert have == want
    assert sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        want, is_leaf=lambda x: isinstance(x, tuple))) == cfg["params"]
