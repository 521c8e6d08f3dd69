#!/usr/bin/env python3
"""STCO sweep benchmark: one cell, one run, on the chip it is started on.

    python3 benchmarks/stco/bench.py --workload signoff.batch --seed 7 \
        --seconds 30 --trace 0

Run from the root of a checkout.  The cell (`BENCHMARK.json` workloads)
names a configuration (`configs/<config>.json`) and a traffic mix
(`traffic/<mix>.json`); `loops/<kind>.py` drives the mix's loop.  Set-up warms
every shape the window runs, then the window measures for `--seconds`.
After it, a sample of the window's answers drawn from the seed is
compared with the plain reference (`reference.py`, `compare.py`).

`--trace 0` prints the cell's end-to-end metrics (host clock); `--trace 1`
takes a profiler trace of the window and prints the per-layer metrics
(`metrics/<name>.py`).  The last line of stdout is one JSON object; the
numbers compared, each beside its limit, are the last lines of stderr and
the result's last key (`checks`).  Without a TPU, or with fewer chips
than the cell needs, it prints no result and exits 2.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import tempfile
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    __package__ = "benchmarks.stco"

from benchmarks.stco import compare, harness, peaks, reference, trace  # noqa: E402


class Run:
    """What a per-layer metric reader sees."""

    def __init__(self, cell, loop, tr, devices, peak):
        self.cell, self.loop, self.trace = cell, loop, tr
        self.devices, self.peak = devices, peak


def _log(name: str, **fields) -> None:
    print(f"{name}: {json.dumps(fields, sort_keys=True)}", flush=True)


def run(args, cell=None, device_check=harness.require_devices,
        compile_cache: bool = True) -> dict:
    """One run.  `cell`, `device_check` and `compile_cache` are for tests
    that drive a run at a small size without a chip."""
    import jax
    import numpy as np

    cell = cell or harness.load_cell(args.workload)
    harness.use_program()
    device = device_check(cell.chips)
    backend_s = harness.process_age_s()          # interpreter, imports, backend
    peak = peaks.peaks(device["kind"])
    if compile_cache:
        from repro.runtime.compile_cache import enable_compile_cache
        _log("compile_cache", dir=enable_compile_cache())
    meter = harness.CompileMeter()
    traced = bool(args.trace)
    loop = harness.loop_class(cell.mix["loop"])(cell, args.seed, traced)
    loop.setup(args.seconds)
    setup_s = harness.process_age_s()
    before = meter.snapshot()
    _log("setup", setup_s=setup_s, backend_s=backend_s, **before)

    trace_dir = tempfile.mkdtemp(prefix="stco_trace_") if traced else None
    try:
        if traced:
            # host spans and device ops only: no Python function tracing
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("stco.window"):
            loop.window(args.seconds)
        if traced:
            jax.profiler.stop_trace()
        after = meter.snapshot()
        _log("window", compilations=after["programs"] - before["programs"],
             compile_s=after["compile_s"] - before["compile_s"],
             cache_loads=after["cache_hits"] - before["cache_hits"],
             attempted=loop.attempted)
        e2e = loop.end_to_end()
        e2e["setup_s"] = setup_s
        device["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
        tr = trace.load(trace_dir) if traced else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics, breakdown = {}, None
    if traced:
        devices = list(range(cell.chips))
        reader_run = Run(cell, loop, tr, devices, peak)
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"])(reader_run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = sum(trace.busy_s(tr, d) for d in devices) / len(devices)
        device["window_s"] = tr.window_s
        breakdown = {"device_ops": trace.top_ops(tr), "idle_gaps": trace.idle_gaps(tr)}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}

    rng = np.random.default_rng((int(args.seed), 0xC0DE))
    prog, picks = loop.sample(rng)
    values = {}
    if prog is not None:
        values = compare.numbers(prog, reference.sampled_columns(cell.config, picks),
                                 cell.config)
    correct, checks = compare.judge(values, cell.limits)
    correct = correct and loop.failed == 0
    out = {"correct": bool(correct), "attempted": int(loop.attempted),
           "failed": int(loop.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
        if c["value"] is not None and not math.isfinite(c["value"]):
            c["value"] = None          # JSON has no infinity; null fails the check
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
