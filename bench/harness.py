"""The harness: finds cells, configurations, traffic mixes, job kinds and
per-layer metric readers by name, and checks the device.

Layout, all found from the names in ``BENCHMARK.json``:

  bench/configs/<config>.json     sizes of one configuration, as run
  bench/workloads/<traffic>.json  one traffic mix; its ``kind`` names
                                  the driver below
  bench/kinds/<kind>.py           ``run(ctx) -> dict`` for one kind of job
  bench/metrics/<metric>.py       ``read(ctx) -> float | None`` for one
                                  per-layer metric

A new cell, configuration or metric is a new file and a new entry; no
file of the harness changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# fixed, inside the checkout: the directory is part of JAX's cache key
CACHE_DIR = ROOT / ".bench" / "jax_cache"
TRACE_DIR = ROOT / ".bench" / "trace"


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell needs."""


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    """The workload entry ``name`` with its configuration and traffic."""
    w = _by_name(bench["workloads"], name, "workload")
    conf = _by_name(bench["configs"], w["config"], "config")
    return {"workload": w,
            "config_entry": conf,
            "config": json.loads((ROOT / conf["file"]).read_text()),
            "traffic": json.loads(
                (BENCH_DIR / "workloads" / f"{w['traffic']}.json").read_text())}


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind(name: str):
    """The driver module of a kind of job: ``bench/kinds/<name>.py``."""
    return _load_module(BENCH_DIR / "kinds" / f"{name}.py",
                        f"bench_kind_{name}")


def metric_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    mod = _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))
    return mod.read


def cell_metrics(bench: dict, workload: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``workload`` reports: those with
    no ``workloads`` key, and those that list it."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def use_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it itself), else the fixed directory in the checkout.
    Every program is cached, however short its compile."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def check_devices(chips: int) -> dict:
    """Platform, kind and count of the devices; NoChip off a TPU or with
    fewer chips than ``chips``.  The program's kernel router has to
    resolve to the compiled Pallas kernels: no fallback is timed."""
    import jax
    from repro.kernels import router
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX found {platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    route = router.resolve()
    if route != "pallas":
        raise NoChip(f"kernel routing resolved to {route!r}, not pallas")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileTimer:
    """Compiles (backend compile events) and their seconds since start."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration
        if event == self._EVENTS[-1]:
            self.compiles += 1
