"""Pieces every run shares: finding files by name, the device, the compile
cache and the count of compiles, and the records a driver hands back."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent       # the benchmark's files
CHECKOUT = ROOT.parent                               # the program's checkout


class RunError(RuntimeError):
    """The run cannot produce a result: no accelerator, too few chips, no
    program, or a file missing."""


@dataclasses.dataclass
class Window:
    """What a driver measured: end-to-end metrics by name, requests or
    calls attempted, the window's length and the counts that per-layer
    readers use."""
    metrics: dict
    attempted: int
    seconds: float
    counts: dict


@dataclasses.dataclass
class Checks:
    """Each compared number as (value, limit), and how many attempts
    failed.  A run is correct when every value is at most its limit."""
    values: dict
    failed: int


def load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise RunError(f"missing file {path}") from None


def load_module(path: pathlib.Path):
    """Import a file by its path (metric readers are named like metrics,
    dots included)."""
    if not path.is_file():
        raise RunError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(root: pathlib.Path, name: str):
    """A cell by its name in ``BENCHMARK.json``: its entry there, with the
    traffic mix (``traffic/<traffic>.json``: the driver and its parameters)
    and the cell's limits (``workloads/<cell>.json``) filled in, and its
    configuration (``configs/<config>.json``)."""
    bench = load_json(root.parent / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise RunError(f"no workload {name!r} in BENCHMARK.json")
    traffic = load_json(root / "traffic" / f"{entry['traffic']}.json")
    cell = {**entry, "driver": traffic["driver"], "traffic": traffic,
            **load_json(root / "workloads" / f"{name}.json")}
    config = load_json(root / "configs" / f"{entry['config']}.json")
    return bench, cell, config


def import_program(name: str):
    """Import a module of the program under test from the checkout's
    ``src``."""
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        raise RunError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    return importlib.import_module(name)


def require_accelerator(chips: int) -> list:
    """The first ``chips`` TPU devices, or RunError: the benchmark never
    falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RunError(f"JAX found no TPU (platform "
                       f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise RunError(f"the cell needs {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices[:chips]


def device_record(devices: list) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def use_compile_cache(directory: pathlib.Path) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program kept, so only a checkout's first run compiles."""
    import jax
    directory.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(directory))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs that JAX lowers: every new jit specialization or
    eager op shape, whether it then compiles or loads from the cache."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1
