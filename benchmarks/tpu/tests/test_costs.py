"""The analytic counts the per-layer metrics divide by."""

import numpy as np
import pytest

from fault_run import benchmark_with_dormant
from tpubench import costs, harness


def _config(name):
    bench = benchmark_with_dormant()
    cell = next(w["name"] for w in bench["workloads"] if w["config"] == name)
    return harness.load_cell(cell, bench)


def test_femnist_flops_per_round():
    cell = _config("femnist_mlp")
    # 6 x 58,430 parameters x (32 clients x 8 steps x 20 samples)
    assert cell.config_mod.flops_per_round(cell.config) == 6 * 58_430 * 5_120
    assert cell.config_mod.flops_per_round(cell.config) == pytest.approx(1.795e9, rel=1e-3)


def test_mamba_flops_per_round():
    cell = _config("mamba2_130m")
    # 6 x 128,983,488 parameters x (8 clients x 4 x 512 tokens)
    assert cell.config_mod.flops_per_round(cell.config) == 6 * 128_983_488 * 16_384
    assert cell.config_mod.flops_per_round(cell.config) == pytest.approx(1.268e13, rel=1e-3)


def test_param_counts_follow_the_widths():
    cfg = _config("femnist_mlp").config
    d, h, c = cfg["input_dim"], cfg["hidden"], cfg["num_classes"]
    assert d * h + h + h * h + h + h * c + c == cfg["params"] == 58_430
    cell = _config("mamba2_130m")
    cfg, dm = cell.config, cell.config_mod.dims(cell.config)
    per_layer = (cfg["d_model"] * dm["proj"] + dm["conv"] * cfg["d_conv"] + dm["conv"]
                 + 3 * dm["heads"] + dm["d_in"] + dm["d_in"] * cfg["d_model"] + cfg["d_model"])
    total = cfg["n_layer"] * per_layer + cfg["vocab_size"] * cfg["d_model"] + cfg["d_model"]
    assert total == cfg["params"] == 128_983_488


@pytest.mark.parametrize("d,rows,itemsize,want", [
    (58_430, 3, 4, 58_430 * 4 + 3 * 58_430 * 4),      # masked aggregate: sent rows only
    (128_983_488, 2, 2, 128_983_488 * (4 + 2 * 2)),   # norm+aggregate: every row, bf16
    (10, 0, 4, 40),                                   # nothing sent: the result alone
])
def test_aggregate_bytes(d, rows, itemsize, want):
    assert costs.aggregate_bytes(d, rows, itemsize) == want


def test_tiny_configs_count_their_params():
    import jax

    for name in ("femnist_mlp", "mamba2_130m"):
        cell = _config(name)
        tiny = cell.config_mod.tiny(cell.config)
        params = jax.eval_shape(cell.config_mod.make_init(tiny), jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
        assert n == tiny["params"], name
