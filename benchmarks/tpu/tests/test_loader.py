"""BENCHMARK.json and the files it names: every cell resolves by name to a
configuration, a traffic mix, an entry, limits and metric readers; the
contract's shapes hold; an unknown device kind is an error."""

import json
import re

import pytest

from fault_run import benchmark_with_dormant
from tpubench import harness

BENCH = harness.load_benchmark()
ALL = benchmark_with_dormant()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", [w["name"] for w in ALL["workloads"]])
def test_cell_resolves(workload):
    cell = harness.load_cell(workload, ALL)
    assert cell.chips in (1, 4)
    for fn in ("setup", "window", "reference", "release", "compile_for_tpu"):
        assert callable(getattr(cell.entry, fn))
    for fn in ("flops_per_round", "aggregate_itemsize", "tiny", "reference", "make_init"):
        assert callable(getattr(cell.config_mod, fn))
    assert {m["name"] for m in cell.end_to_end} == {"rounds_per_s", "setup_s"}
    assert cell.per_layer or workload not in WORKLOADS, "every cell reports a per-layer metric"
    for spec, reader in cell.per_layer:
        assert callable(reader.read)
    limits = harness.load_limits(workload)
    assert all(v >= 0 for v in limits.values())


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for item in BENCH["configs"] + BENCH["workloads"] + metrics:
        assert NAME.match(item["name"]), item["name"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", [])) <= set(WORKLOADS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(WORKLOADS) // 2)


def test_configs_hold_their_sizes():
    for c in BENCH["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg), c["name"]
        assert cfg["params"] > 0


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v9 imaginary")


def test_unknown_workload_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such-cell", BENCH)
