"""Shared pieces of the benchmark: the cell's files, the device check,
compile counting, the set-up clock, and reading a study's rows.

Everything is found by name: the workload entry in `BENCHMARK.json`
names its configuration (`configs/<config>.json`), its traffic mix
(`traffic/<mix>.json`), whose `loop` kind is driven by `loops/<kind>.py`;
the cell's limits are `limits/<workload>.json` and each per-layer metric
is read by `metrics/<metric>.py`.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
_IMPORTED_AT = time.time()


class BenchError(RuntimeError):
    """The run cannot be measured (wrong device, missing file, ...)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list        # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def _for_cell(metrics, name):
    return [m for m in metrics if "workloads" not in m or name in m["workloads"]]


def load_cell(name: str, root: Path = ROOT, here: Path = HERE) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        mix=json.loads((here / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((here / "limits" / f"{name}.json").read_text()),
        end_to_end=_for_cell(bench["end_to_end"], name),
        per_layer=_for_cell(bench["per_layer"], name))


def _module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE):
    """`read(run)` of `metrics/<name>.py`: returns a number or None."""
    return _module(here / "metrics" / f"{name}.py", f"stco_metric_{name}").read


def loop_class(kind: str, here: Path = HERE):
    """`Loop` of `loops/<kind>.py`: the loop that drives a mix's window."""
    return _module(here / "loops" / f"{kind}.py", f"stco_loop_{kind}").Loop


def process_age_s() -> float:
    """Seconds since this process started (kernel clock), or since this
    module was imported where /proc is not there."""
    try:
        start_ticks = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time() - _IMPORTED_AT


def use_program():
    """Put the checkout's `src` on the path: the system under test."""
    src = str(ROOT / "src")
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"no system under test: {src}/repro is not in this checkout")
    if src not in sys.path:
        sys.path.insert(0, src)


class CompileMeter:
    """Compilations and persistent-cache loads, from JAX's own events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "programs": self.programs,
                "cache_hits": self.cache_hits}


def require_devices(chips: int) -> dict:
    """The accelerator as JAX reports it; refuses anything but a TPU with
    at least `chips` chips (JAX falls back to the CPU when the TPU fails)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {info['platform']!r}")
    if info["count"] < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {info['count']}")
    info["count"] = chips
    return info


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def program_columns(batch, rows) -> dict:
    """The rows `rows` of a `DesignBatch`, as host columns shaped like
    `reference.study_columns` output."""
    from . import reference
    idx = np.asarray(rows, np.int64)
    get = lambda x: np.asarray(x)[idx]
    out = {k: get(getattr(batch, k)).astype(np.float64)
           for k in reference.SCORED + reference.TIMED}
    out.update(
        tech=[batch.tech_names[i] for i in get(batch.tech_idx)],
        scheme=[batch.scheme_names[i] for i in get(batch.scheme_idx)],
        layers=get(batch.layers).astype(np.float64),
        corners={k: get(v).astype(np.float64) for k, v in batch.corners.items()},
        manufacturable=get(batch.manufacturable).astype(bool),
        feasible=get(batch.feasible).astype(bool),
        valid=get(batch.valid).astype(bool))
    return out
