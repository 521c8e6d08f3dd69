"""Benchmark driver: one function per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (comment lines start with #).

  PYTHONPATH=src python -m benchmarks.run [--only fig3,fig8,...] \
      [--json BENCH_fused_rc.json]

``--json`` additionally writes every bench's machine-readable metrics
(benches that return a dict) plus run metadata to one JSON file — CI runs
``--only fused_rc --json BENCH_fused_rc.json`` on every PR and uploads it
as an artifact, seeding the performance trajectory across PRs.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback

from . import (bench_fig3_routing, bench_fig8_transient, bench_fig9_scaling,
               bench_fused_row_cycle, bench_kernels, bench_roofline,
               bench_serve, bench_sharded_sweep, bench_strap_cache,
               bench_table1)

ALL = {
    "table1": bench_table1.main,
    "fig3": bench_fig3_routing.main,
    "fig8": bench_fig8_transient.main,
    "fused_rc": bench_fused_row_cycle.main,
    "sharded_sweep": bench_sharded_sweep.main,
    "serve": bench_serve.main,
    "fig9": bench_fig9_scaling.main,
    "kernels": bench_kernels.main,
    "strap_cache": bench_strap_cache.main,
    "roofline": bench_roofline.main,
}


def _run_meta() -> dict:
    import jax
    return {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "python": platform.python_version(),
        "platform": platform.platform(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="write machine-readable metrics of the selected "
                         "benches (those returning a dict) to PATH")
    args = ap.parse_args()
    names = [n for n in args.only.split(",") if n] or list(ALL)
    print("name,us_per_call,derived")
    failures = []
    metrics: dict = {}
    for name in names:
        try:
            out = ALL[name]()
            if isinstance(out, dict):
                metrics[name] = out
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if args.json:
        payload = {"meta": _run_meta(), "benches": metrics,
                   "failed": failures}
        with open(args.json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)
    if failures:
        print(f"# FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
