"""Sharded sweep throughput vs device count (forced host devices).

Measures `dse.sweep(space, sharding=mesh)` points/sec on the paper grid
fanned out with Monte-Carlo samples, at several forced-host-platform
device counts.  Each count runs in a subprocess because
`--xla_force_host_platform_device_count` must be set before the first
jax import.  The 1-device run is the baseline; the scaling record
(`best_scaling_vs_1dev`) is what CI tracks in BENCH_sharded_sweep.json.

On shared CPU runners the devices are threads over a few cores, so the
interesting signal is "does sharding beat the sequential chunk loop at
all" (>1x), not linear scaling — real meshes (one accelerator per
device, multi-host) are where the slab-per-device dispatch pays off.
This bench is a CPU-only rehearsal: its children are pinned to
JAX_PLATFORMS=cpu.  The sharded sweep on chips is `chip_smoke.py --chips 4`.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from .common import emit

DEVICE_COUNTS = (1, 2, 4, 8)
MC_SAMPLES = 64

_CHILD = """
import json, time
import jax
from repro.core import dse
from repro.core.space import DesignSpace
from repro.launch.mesh import make_sweep_mesh

space = DesignSpace.paper_grid().with_mc(samples=%d, key=0)
mesh = make_sweep_mesh()
run = lambda: dse.sweep(space, sharding=mesh)
batch = run()                                    # compile
jax.block_until_ready(batch.trc_ns)
ts = []
for _ in range(3):
    t0 = time.perf_counter()
    jax.block_until_ready(run().trc_ns)
    ts.append(time.perf_counter() - t0)
pareto = lambda: jax.block_until_ready(dse.pareto_mask(batch, sharding=mesh))
pareto()                                         # compile
pts = []
for _ in range(3):
    t0 = time.perf_counter()
    pareto()
    pts.append(time.perf_counter() - t0)
print(json.dumps({"ndev": jax.device_count(), "points": len(space),
                  "wall_s": min(ts), "pareto_wall_s": min(pts)}))
"""

# the elastic driver's deterministic recovery cost: one injected host
# drop at slab 1 of 4 recomputes exactly one slab -> 0.25, whatever the
# hardware — a CORRECTNESS-OF-RECOVERY gate (lower is better), not a
# throughput number
_ELASTIC_CHILD = """
import json
import jax
from repro.core.space import DesignSpace
from repro.launch import elastic
from repro.launch.mesh import make_sweep_mesh
from repro.runtime.fault import FailureInjector

space = DesignSpace.paper_grid().with_mc(samples=%d, key=0)
batch, report = elastic.elastic_sweep(
    space, make_sweep_mesh(),
    injector=FailureInjector(schedule={1: "drop:host0"}))
print(json.dumps({"ndev": jax.device_count(),
                  "resume_overhead_frac": report.resume_overhead_frac,
                  "restarts": report.restarts,
                  "device_history": report.device_history}))
"""


def _child_env(ndev: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # never probe for TPU hardware
    # our forced count goes LAST: with duplicated flags the later one
    # wins, so a pre-existing forced count must not override the bench's
    env["XLA_FLAGS"] = " ".join(
        [env.get("XLA_FLAGS", ""),
         f"--xla_force_host_platform_device_count={ndev}"]).strip()
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if "PYTHONPATH" in env else "")
    return env


def main() -> dict:
    per_device: dict = {}
    for ndev in DEVICE_COUNTS:
        r = subprocess.run([sys.executable, "-c", _CHILD % MC_SAMPLES],
                           capture_output=True, text=True,
                           env=_child_env(ndev), timeout=600)
        if r.returncode != 0:
            raise RuntimeError(f"sharded bench child (ndev={ndev}) failed:\n"
                               f"{r.stderr[-2000:]}")
        rec = json.loads(r.stdout.strip().splitlines()[-1])
        assert rec["ndev"] == ndev, rec
        pts_per_s = rec["points"] / rec["wall_s"]
        rec["points_per_s"] = pts_per_s
        rec["pareto_points_per_s"] = rec["points"] / rec["pareto_wall_s"]
        per_device[str(ndev)] = rec
        emit(f"sharded_sweep_d{ndev}", rec["wall_s"] * 1e6,
             f"points_per_s={pts_per_s:,.0f}")

    base = per_device["1"]["points_per_s"]
    best_ndev = max(per_device, key=lambda k: per_device[k]["points_per_s"])
    scaling = per_device[best_ndev]["points_per_s"] / base
    emit("sharded_sweep_scaling", 0.0,
         f"best={best_ndev}dev;vs_1dev={scaling:.2f}x")

    # the gated pareto throughput is the widest mesh's (the config the
    # sharded dominance engine exists for)
    max_ndev = str(max(DEVICE_COUNTS))
    pareto_pts_per_s = per_device[max_ndev]["pareto_points_per_s"]
    emit(f"sharded_pareto_d{max_ndev}",
         per_device[max_ndev]["pareto_wall_s"] * 1e6,
         f"points_per_s={pareto_pts_per_s:,.0f}")

    r = subprocess.run(
        [sys.executable, "-c", _ELASTIC_CHILD % MC_SAMPLES],
        capture_output=True, text=True,
        env=_child_env(max(DEVICE_COUNTS)), timeout=600)
    if r.returncode != 0:
        raise RuntimeError(f"elastic bench child failed:\n"
                           f"{r.stderr[-2000:]}")
    erec = json.loads(r.stdout.strip().splitlines()[-1])
    emit("elastic_resume_overhead", 0.0,
         f"frac={erec['resume_overhead_frac']:.2f};"
         f"restarts={erec['restarts']}")

    return {
        "mc_samples": MC_SAMPLES,
        "points": per_device["1"]["points"],
        "device_counts": list(DEVICE_COUNTS),
        "per_device": per_device,
        "best_device_count": int(best_ndev),
        "best_scaling_vs_1dev": scaling,
        "sharded_pareto_points_per_s": pareto_pts_per_s,
        "elastic_resume_overhead_frac": erec["resume_overhead_frac"],
        "elastic_restarts": erec["restarts"],
        "elastic_device_history": erec["device_history"],
    }


if __name__ == "__main__":
    main()
