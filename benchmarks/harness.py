"""What run.py and the drivers share: the cell's plan as BENCHMARK.json and
the data files state it, the objects a driver and a reader are handed, and
the look-up of files by name."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - _T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Plan:
    """What BENCHMARK.json and the cell's files say about one cell."""

    root: str
    benchmark: dict
    cell: dict
    config_entry: dict
    config: dict
    workload: dict

    def find(self, *rel: str) -> str:
        """A benchmark file by relative path: under --root first (where a
        later PR, or a test, brings its own), else beside this file."""
        for base in (self.root, CHECKOUT):
            path = os.path.join(base, *rel)
            if os.path.exists(path):
                return path
        raise BenchmarkError(f"no such benchmark file: {os.path.join(*rel)}")

    def metrics(self, section: str) -> list[dict]:
        """The section's metrics that this cell reports."""
        return [
            m for m in self.benchmark[section]
            if "workloads" not in m or self.cell["name"] in m["workloads"]
        ]


def load_plan(root: str, workload: str) -> Plan:
    benchmark = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise BenchmarkError(
            f"BENCHMARK.json has no workload {workload!r}; it has "
            f"{sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in benchmark["configs"]}
    if cell["config"] not in configs:
        raise BenchmarkError(f"workload {workload} names no known config")
    entry = configs[cell["config"]]
    plan = Plan(root, benchmark, cell, entry, {}, {})
    plan.config = load_json(plan.find(entry["file"]))
    plan.workload = load_json(
        plan.find("benchmarks", "workloads", workload + ".json")
    )
    if plan.workload.get("config") != cell["config"]:
        raise BenchmarkError(
            f"{workload}.json and BENCHMARK.json disagree on the config"
        )
    return plan


def load_module(plan: Plan, kind: str, name: str):
    path = plan.find("benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Context:
    """What a driver is given."""

    plan: Plan
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    state_dir: str  # emptied at the start of every run; inside the checkout
    config: dict  # the configuration as run (rehearsal sizes merged in)
    traffic: dict  # the cell's traffic parameters

    def log(self, msg: str) -> None:
        log(msg)


@dataclass
class Reading:
    """What a per-layer metric's reader is given."""

    config: dict
    workload: dict
    device_kind: str
    peaks: Optional[dict]
    window: dict
    trace: Any  # trace_reduce.TraceSummary, or None without --trace 1


@dataclass
class Check:
    """One number compared, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def open_cell(root: str, workload: str, rehearsal: bool):
    """(plan, driver) with the process's environment set for jax's first
    import: the caches' place and, in a rehearsal, the driver's CPU
    settings."""
    plan = load_plan(os.path.abspath(root), workload)
    place_caches(rehearsal)
    driver = load_module(plan, "drivers", plan.workload["driver"])
    if rehearsal:
        # before jax is imported: interpreter-mode kernels, CPU devices
        os.environ.update(driver.rehearsal_env())
    return plan, driver


def make_context(plan: Plan, seed: int, seconds: float, trace: bool,
                 rehearsal: bool) -> "Context":
    """A run's context, its state directory emptied."""
    config = dict(plan.config)
    traffic = dict(plan.workload.get("traffic", {}))
    if rehearsal:
        config.update(plan.config.get("rehearsal", {}))
        traffic.update(plan.workload.get("rehearsal_traffic", {}))
    state_dir = os.path.join(plan.root, ".bench_state", plan.cell["name"])
    shutil.rmtree(state_dir, ignore_errors=True)
    os.makedirs(state_dir)
    return Context(plan=plan, seed=seed, seconds=seconds, trace=trace,
                   rehearsal=rehearsal, state_dir=state_dir, config=config,
                   traffic=traffic)


def place_caches(rehearsal: bool) -> None:
    """Everything the run caches goes inside the checkout, at a fixed path
    (the path is part of the compile cache's key)."""
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(CHECKOUT, ".jax_cache")
    )
    if rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")


def device_info(jax) -> dict:
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(jax) -> int:
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def start_trace(jax, trace_dir: str, options: dict) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = int(options.get("python_tracer_level", 0))
    opts.host_tracer_level = int(options.get("host_tracer_level", 2))
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
