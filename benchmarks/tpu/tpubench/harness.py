"""Loader and device plumbing of the TPU benchmark, driven by data.

Everything a cell needs is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``configs/<config>.json`` plus
the plain reference ``configs/<config>.py`` beside it) and its traffic
(``traffic/<traffic>.json``); the traffic names the entry that drives the
program (``entries/<entry>.py``); the cell's own file
(``cells/<cell>.json``) holds its nominal rate, which fixes the window's
work for a given ``--seconds``, and the limits of its comparison; each
per-layer metric is a reader of its own (``metrics/<metric>.py``).  Adding
a configuration, a traffic mix, a cell or a metric adds files and entries;
no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
PROGRAM_SRC = ROOT / "src"
# fixed path inside the checkout: the path is part of every cache key
CACHE_DIR = BENCH_DIR / ".jax_cache"
OUT_DIR = BENCH_DIR / "out"


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, msg: str, code: int = 2):
        super().__init__(code)
        self.msg = msg


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    config_mod: ModuleType
    traffic: dict
    spec: dict
    entry: ModuleType
    end_to_end: list
    per_layer: list = field(default_factory=list)   # [(spec, reader module)]


def load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError("BENCHMARK.json not found at the checkout's root")
    return json.loads(path.read_text())


def _by_name(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise BenchError(f"unknown {what} {name!r}; known: {[i['name'] for i in items]}")


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench: dict | None = None) -> Cell:
    bench = load_benchmark() if bench is None else bench
    w = _by_name(bench["workloads"], workload, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    config = json.loads((ROOT / c["file"]).read_text())
    cfg_py = (ROOT / c["file"]).with_suffix(".py")
    config_mod = load_module(cfg_py, f"tpubench_config_{w['config']}")
    traffic = json.loads((BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    entry = load_module(BENCH_DIR / "entries" / f"{traffic['entry']}.py",
                        f"tpubench_entry_{traffic['entry']}")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [
        (m, load_module(BENCH_DIR / "metrics" / f"{m['name']}.py",
                        f"tpubench_metric_{m['name']}"))
        for m in bench["per_layer"] if _applies(m, workload)
    ]
    return Cell(workload, int(w["chips"]), config, config_mod, traffic,
                load_cell_spec(workload), entry, e2e, per_layer)


def load_peaks(device_kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json "
                         f"(known: {sorted(table)})")
    return table[device_kind]


def load_cell_spec(workload: str) -> dict:
    path = BENCH_DIR / "cells" / f"{workload}.json"
    if not path.is_file():
        raise BenchError(f"no cell file for {workload!r}: {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_limits(workload: str) -> dict:
    """The limit of every number the cell's comparison yields, each set
    between measured readings (``cells/<workload>.json``)."""
    return {k: float(v["limit"]) for k, v in load_cell_spec(workload)["limits"].items()}


def require_program() -> None:
    """The system under test lives in the checkout's ``src/``."""
    import sys

    if not (PROGRAM_SRC / "repro" / "__init__.py").is_file():
        raise BenchError("the program (src/repro) is not in this checkout")
    if str(PROGRAM_SRC) not in sys.path:
        sys.path.insert(0, str(PROGRAM_SRC))


def require_devices(chips: int) -> list:
    """The cell's TPU devices, or a BenchError: never a CPU fallback."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no devices: {e}") from None
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX found {len(devices)}")
    return devices[:chips]


def device_record(devices: list) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_memory(devices: list) -> int:
    """``peak_bytes_in_use`` of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at its fixed path in the checkout,
    every program cached: nothing in the environment moves it."""
    import jax

    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts program builds and persistent-cache loads, so a window can
    report how many programs it compiled (builds minus loads)."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.builds = 0
        self.hits = 0

        def on_duration(event, duration, **kw):
            if event == self.BUILD:
                self.builds += 1

        def on_event(event, **kw):
            if event == self.HIT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.builds, self.hits

    def since(self, snap: tuple) -> dict:
        builds, hits = self.builds - snap[0], self.hits - snap[1]
        return {"compiled": builds - hits, "loaded_from_cache": hits}
