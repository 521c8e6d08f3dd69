"""Two-process `jax.distributed` integration smoke for the sweep fabric.

`launch.shard`'s multi-process story has three load-bearing claims:

  1. the DesignSpace lowering is deterministic and host-replicated, so
     every process assembles bit-identical operand batches on its own;
  2. `put_global` assembles a global array one addressable shard at a
     time via `jax.make_array_from_callback`, each shard bit-identical
     to the corresponding rows of the host-replicated batch;
  3. per-row evaluation + scoring is slab-independent, so the rows a
     host computes are bit-identical to the same rows of a single-host
     sweep — which is what makes the union over hosts THE sweep.

This module proves all three under a REAL `jax.distributed.initialize`
cluster: a coordinator + worker pair on localhost (the `run_smoke`
parent picks a free port and spawns both), each child asserting the
shard contents of `put_global` against the host batch and its own point
slab against the full single-host oracle, bit for bit.

One honest limitation, empirically pinned by this smoke's development:
the CPU backend refuses jit execution over arrays spanning processes
("Multiprocess computations aren't implemented on the CPU backend"), so
the cross-process dispatch itself only executes on GPU/TPU clusters.
On CPU CI the children therefore dispatch their slabs on their LOCAL
device mesh — which, by claim 3 (asserted, not assumed), is the same
computation the global mesh would shard across hosts.

This is a CPU-only rehearsal: the children are pinned to
JAX_PLATFORMS=cpu with forced host devices and never touch a chip.

CLI:  python -m repro.launch.multiproc --smoke         (the CI entry)
      ... --smoke --mc 16 --local-devices 4            (bigger variant)
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
from pathlib import Path

__all__ = ["run_smoke"]

_SRC_DIR = Path(__file__).resolve().parents[2]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _child_main(coordinator: str, num_processes: int, process_id: int,
                mc: int) -> None:
    """One cluster member: initialize distributed JAX FIRST, then verify
    the sharded-sweep multi-process contract and emit one JSON line."""
    import jax
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    import numpy as np
    from jax.sharding import Mesh

    from ..core import dse, transient
    from ..core.space import DesignSpace
    from . import shard

    if jax.process_count() != num_processes:
        raise SystemExit(f"process_count {jax.process_count()} != "
                         f"{num_processes} — distributed init did not take")
    gdevs, ldevs = jax.devices(), jax.local_devices()
    if len(gdevs) <= len(ldevs):
        raise SystemExit(f"global devices ({len(gdevs)}) must exceed local "
                         f"({len(ldevs)}) — the mesh does not span processes")
    gmesh = Mesh(np.asarray(gdevs), ("batch",))
    gsharding = shard.sweep_sharding(gmesh)
    lmesh = Mesh(np.asarray(ldevs), ("batch",))

    spaces = [
        ("targets", DesignSpace.paper_targets()),
        ("targets-mc", DesignSpace.paper_targets().with_mc(mc)),
        ("replica-mc", DesignSpace.paper_targets().with_replica().with_mc(mc)),
    ]
    checks = {}
    for label, space in spaces:
        plan = dse.plan_sweep(space)
        # claim 2: put_global's make_array_from_callback path — every
        # addressable shard of the global operand array must equal the
        # corresponding rows of the host-replicated padded batch
        core = list(plan.operands[:6])
        b = core[0].shape[0]
        target = shard._dispatch_target(b, len(gdevs),
                                        transient.DEFAULT_B_CHUNK)
        for x in transient._pad_operands(core, target - b):
            gx = shard.put_global(x, gsharding)
            host = np.asarray(x)
            if gx.shape != host.shape:
                raise SystemExit(f"{label}: global shape {gx.shape} != "
                                 f"host {host.shape}")
            for s in gx.addressable_shards:
                if not np.array_equal(np.asarray(s.data), host[s.index]):
                    raise SystemExit(
                        f"{label}: addressable shard {s.index} of the "
                        "global operand array differs from the "
                        "host-replicated batch — put_global broke")
        # claims 1+3: this process's point slab, computed here from its
        # own (independently lowered) plan, must be bit-identical to the
        # single-host oracle's rows
        oracle = dse.sweep(space)
        n = len(plan.sp)
        lo = process_id * n // num_processes
        hi = (process_id + 1) * n // num_processes
        cols = shard.sharded_sweep_columns(plan, lmesh, rows=(lo, hi))
        bad = [k for k, v in cols.items()
               if not np.array_equal(np.asarray(v),
                                     np.asarray(getattr(oracle, k))[lo:hi])]
        if bad:
            raise SystemExit(f"{label}: slab [{lo}, {hi}) NOT bit-identical "
                             f"to the single-host sweep: {bad}")
        checks[label] = {"points": n, "rows": [lo, hi]}
    print(json.dumps({"process": process_id, "ok": True,
                      "global_devices": len(gdevs),
                      "local_devices": len(ldevs), "checks": checks}),
          flush=True)


def run_smoke(num_processes: int = 2, mc: int = 8, local_devices: int = 2,
              timeout_s: float = 600.0) -> None:
    """Launch the coordinator + worker children and verify their reports."""
    addr = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # last flag wins, so the forced per-process device count survives any
    # XLA_FLAGS the caller exported
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count="
                          f"{local_devices}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(_SRC_DIR), env.get("PYTHONPATH")) if p)
    procs = [subprocess.Popen(
        [sys.executable, "-m", "repro.launch.multiproc", "--child",
         "--coordinator", addr, "--num-processes", str(num_processes),
         "--process-id", str(i), "--mc", str(mc)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i in range(num_processes)]
    results, failures = [], []
    for i, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise SystemExit(f"multiproc smoke: process {i} timed out after "
                             f"{timeout_s:.0f}s")
        if p.returncode != 0:
            failures.append(f"process {i} rc={p.returncode}:\n"
                            f"{out.strip()}\n{err.strip()[-2000:]}")
            continue
        lines = [ln for ln in out.splitlines() if ln.strip()]
        results.append(json.loads(lines[-1]))
    if failures:
        raise SystemExit("multiproc smoke FAILED:\n" + "\n---\n".join(failures))

    for r in results:
        if not r.get("ok"):
            raise SystemExit(f"multiproc smoke: process {r['process']} "
                             f"reported not-ok: {r}")
    # the per-process slabs must tile every space's full point range —
    # a smoke where both processes checked the same rows proves nothing
    for label in results[0]["checks"]:
        slabs = sorted(r["checks"][label]["rows"] for r in results)
        n = results[0]["checks"][label]["points"]
        covered = slabs[0][0] == 0 and slabs[-1][1] == n and all(
            a[1] == b[0] for a, b in zip(slabs, slabs[1:]))
        if not covered:
            raise SystemExit(f"multiproc smoke: slabs {slabs} do not tile "
                             f"[0, {n}) on {label}")
        print(f"{label}: {n} points tiled over {len(results)} processes "
              f"{slabs} — each slab bit-identical to the single-host sweep")
    print(f"multiproc smoke: OK ({num_processes} processes x "
          f"{local_devices} devices, coordinator {addr})")


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="run the 2-process integration smoke (parent)")
    parser.add_argument("--child", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--coordinator", default=None)
    parser.add_argument("--num-processes", type=int, default=2)
    parser.add_argument("--process-id", type=int, default=0)
    parser.add_argument("--mc", type=int, default=8,
                        help="MC samples for the with_mc spaces")
    parser.add_argument("--local-devices", type=int, default=2,
                        help="forced CPU devices per process")
    parser.add_argument("--timeout", type=float, default=600.0)
    args = parser.parse_args(argv)
    if args.child:
        _child_main(args.coordinator, args.num_processes, args.process_id,
                    args.mc)
    elif args.smoke:
        run_smoke(num_processes=args.num_processes, mc=args.mc,
                  local_devices=args.local_devices, timeout_s=args.timeout)
    else:
        parser.print_help()


if __name__ == "__main__":
    main()
