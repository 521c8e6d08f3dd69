#!/usr/bin/env python3
"""Readings that set the limits of `correct`, for one cell, on the chip.

    python3 benchmarks/stco/control.py --workload signoff.batch \
        --seeds 11 12 13 --control-seeds 11 12 13 --out readings.json

For each seed: the cell's own loop at its own size (a window of
`--seconds`, 0 by default: one study), then the compared
numbers of a sample drawn as a benchmark run draws it — the program
against the float64 reference (lower readings).  For each control seed:
the same rows computed by the reference in bfloat16, put in the
program's place, against the float64 reference (upper readings).  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
    __package__ = "benchmarks.stco"

import numpy as np  # noqa: E402

from benchmarks.stco import compare, harness, reference  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool, warm: bool) -> dict:
    """One seed's readings; `warm` runs the loop's set-up (the cell's
    shapes stay compiled for the seeds after the first)."""
    import ml_dtypes
    loop = harness.loop_class(cell.mix["loop"])(cell, seed, traced=False)
    if warm:
        loop.setup(seconds)
    loop.window(seconds)
    prog, picks = loop.sample(np.random.default_rng((int(seed), 0xC0DE)))
    t0 = time.perf_counter()
    ref = reference.sampled_columns(cell.config, picks)
    out = {"seed": seed, "rows": len(ref["tech"]), "attempted": loop.attempted,
           "failed": loop.failed, "reference_s": time.perf_counter() - t0,
           "program": compare.numbers(prog, ref, cell.config)}
    if control:
        t0 = time.perf_counter()
        low = reference.sampled_columns(cell.config, picks, dtype=ml_dtypes.bfloat16)
        out["control_s"] = time.perf_counter() - t0
        out["control"] = compare.numbers(low, ref, cell.config)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.use_program()
    try:
        harness.require_devices(cell.chips)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for i, seed in enumerate(args.seeds):
        r = readings(cell, seed, args.seconds, seed in args.control_seeds, warm=i == 0)
        print(json.dumps(r), flush=True)
        rows.append(r)
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   "control_min": min((r["control"][k] for r in rows if "control" in r),
                                      default=None)}
               for k in compare.NUMBERS}
    print(json.dumps({"workload": args.workload, "summary": summary}), flush=True)
    if args.out:
        Path(args.out).write_text(json.dumps({"runs": rows, "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
