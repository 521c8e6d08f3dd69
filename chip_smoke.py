#!/usr/bin/env python3
"""On-chip smoke of the STCO design-space sweep, in one process.

    python3 chip_smoke.py              # one TPU chip: sweep, anchors, service
    python3 chip_smoke.py --chips 4    # four chips: sharded sweep, Pareto

Phases on one chip, in order:

1. device  — refuse to run unless JAX's first device is a TPU (JAX falls
   back to the CPU when the TPU backend fails to start).
2. sweep   — `dse.sweep` of the paper grid with replica-closed timing and
   4096 Monte-Carlo draws per point (73 design points, 299,008 design
   rows, 598,016 kernel rows) through the default
   `backend="auto"`, which must be the compiled Pallas kernel.  Its raw
   events are compared row by row with `backend="ref"` on the same chip:
   identical NaN pattern, event times within one dt, dv_sense to rtol 1e-3.
3. anchors — Table-1 anchors of the nominal paper grid (tolerances of
   tests/test_paper_numbers.py) and the paper's selected design.
4. service — a `DSEService` answers concurrent clients (sweep, MC yield,
   replica space) in one window, then a repeat from its memo; every
   answer bit-identical to a direct `dse.sweep`.

With `--chips 4` only the sharded path runs: `dse.sweep(space,
sharding=mesh)` and `dse.pareto_mask(batch, sharding=mesh)` on four
devices, each compared bit for bit with the single-device result.

The last line of stdout is `{"ok": true, "device": {...}}` on success and
nothing of the sort on failure (exit code 1).  Timings printed on earlier
lines are smoke timings of one run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))

STUDY_SAMPLES = 4096
SELECTED = "aos / sel_strap @ 87 -> 2.60 Gb/mm2, tRC 10.50 ns"
# (tech, scheme, layers, tRC ns, density Gb/mm2 or None): Table 1
ANCHORS = (("si", "sel_strap", 137, 10.9, 2.6),
           ("aos", "sel_strap", 87, 10.5, 2.6),
           ("d1b", "direct", 1, 21.3, None))
TRC_RTOL, DENSITY_RTOL = 0.02, 0.01
DV_RTOL, DV_ATOL = 1e-3, 1e-5


class SmokeError(RuntimeError):
    """A phase found a wrong result."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeError(msg)


def log(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields, sort_keys=True)}", flush=True)


def compile_totals() -> dict:
    """Programs compiled, their seconds, and persistent-cache loads since
    `repro.obs` was imported (its process totals of JAX's compile events)."""
    from repro import obs
    return obs.summary()["compile"]


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def phase_device(chips: int) -> dict:
    info = device_info()
    _check(info["platform"] == "tpu",
           f"no TPU: first device is {info['platform']!r}")
    _check(info["count"] >= chips,
           f"asked for {chips} chips, JAX sees {info['count']}")
    return info


def compare_events(evt, evt_ref, dt: float) -> dict:
    """Row-by-row kernel check against the oracle: identical NaN pattern
    of the event times, times within one integration step, dv_sense to
    rtol 1e-3.  Raises SmokeError; returns the agreement counts.

    dv_sense is sampled at the SA-enable step, so where float noise moves
    that crossing by one step the two engines sample the (rising) signal
    one step apart.  Such rows are counted apart: their dv_sense must lie
    on the side the later or earlier sample implies, and the largest
    relative gap is reported.
    """
    import numpy as np
    evt, evt_ref = np.asarray(evt), np.asarray(evt_ref)
    _check(evt.shape == evt_ref.shape,
           f"event shapes differ: {evt.shape} vs {evt_ref.shape}")
    t, t_ref = evt[:, [0, 2, 3]], evt_ref[:, [0, 2, 3]]
    nan_rows = int((np.isnan(t) != np.isnan(t_ref)).any(axis=1).sum())
    _check(nan_rows == 0, f"{nan_rows} rows differ in their NaN pattern")
    steps = np.where(np.isnan(t), 0, np.rint(t / dt))
    steps_ref = np.where(np.isnan(t_ref), 0, np.rint(t_ref / dt))
    step_diff = steps - steps_ref
    _check(np.abs(step_diff).max(initial=0) <= 1,
           f"event times differ by {int(np.abs(step_diff).max())} steps")
    dv, dv_ref = evt[:, 1], evt_ref[:, 1]
    close = np.isclose(dv, dv_ref, rtol=DV_RTOL, atol=DV_ATOL, equal_nan=True)
    fire_shift = step_diff[:, 0]
    same_step = fire_shift == 0
    bad = same_step & ~close
    _check(not bad.any(),
           f"{int(bad.sum())} rows: dv_sense beyond rtol {DV_RTOL}")
    tol = DV_RTOL * np.abs(dv_ref) + DV_ATOL
    wrong_side = (((fire_shift > 0) & (dv < dv_ref - tol))
                  | ((fire_shift < 0) & (dv > dv_ref + tol)))
    _check(not wrong_side.any(),
           f"{int(wrong_side.sum())} rows: dv_sense moved against the "
           "shift of their SA-enable step")
    shifted = ~same_step
    rel = np.abs(dv - dv_ref) / np.maximum(np.abs(dv_ref), DV_ATOL)
    return {"rows": int(evt.shape[0]),
            "rows_bit_identical": int((
                (evt == evt_ref) | (np.isnan(evt) & np.isnan(evt_ref))
            ).all(axis=1).sum()),
            "rows_one_step_off": int((step_diff != 0).any(axis=1).sum()),
            "rows_fire_step_off": int(shifted.sum()),
            "fire_step_off_max_dv_rel": float(rel[shifted].max(initial=0.0)),
            "same_step_max_dv_rel": float(rel[same_step].max(initial=0.0)),
            "same_step_median_dv_rel": float(np.median(rel[same_step]))
            if same_step.any() else 0.0}


def _kernel_is_compiled(operands, backend: str) -> bool:
    """True when the jitted engine lowers to a Mosaic custom call (the
    compiled Pallas kernel), False when it is the oracle or interpreted."""
    import jax
    from repro.core import transient
    from repro.kernels import ops
    shapes = [jax.ShapeDtypeStruct((transient.DEFAULT_B_CHUNK,) + x.shape[1:],
                                   x.dtype) for x in operands[:6]]
    text = ops.row_cycle_fused.lower(
        *shapes, transient.DT_NS, transient.N_ACT_STEPS,
        transient.N_RESTORE_STEPS, transient.N_PRE_STEPS,
        backend=backend).as_text()
    return "tpu_custom_call" in text


def study_space(samples: int):
    from repro.core.space import DesignSpace
    return DesignSpace.paper_grid().with_replica().with_mc(samples=samples,
                                                           key=0)


def _timed_sweep(space, **kw):
    import jax
    from repro.core import dse
    before = compile_totals()
    t0 = time.perf_counter()
    batch = dse.sweep(space, **kw)
    jax.block_until_ready(batch.trc_ns)
    wall = time.perf_counter() - t0
    after = compile_totals()
    return batch, {"wall_s": wall,
                   "compile_s": after["seconds"] - before["seconds"],
                   "programs": after["programs"] - before["programs"],
                   "cache_hits": after["cache_loads"] - before["cache_loads"]}


def phase_sweep(samples: int = STUDY_SAMPLES, backend: str = "auto") -> dict:
    """Study-size sweep through the entry point, kernel events vs ref.
    On a TPU the engine must lower to the compiled Pallas kernel."""
    from repro.core import batch as batch_mod
    from repro.core import dse, transient

    space = study_space(samples)
    t0 = time.perf_counter()
    plan = dse.plan_sweep(space)
    plan_s = time.perf_counter() - t0
    compiled = _kernel_is_compiled(plan.operands, backend)
    _check(compiled or device_info()["platform"] != "tpu",
           f"backend={backend!r} did not lower to the compiled Pallas kernel")

    batch, cold = _timed_sweep(space, backend=backend)
    batch_warm, warm = _timed_sweep(space, backend=backend)
    _check(batch_mod.batches_identical(batch, batch_warm),
           "two identical sweeps disagree")

    evt = transient.row_cycle_events(plan.operands, backend=backend)
    evt_ref = transient.row_cycle_events(plan.operands, backend="ref")
    agreement = compare_events(evt, evt_ref, transient.DT_NS)
    # the compared events are the ones the sweep scored
    rescored = dse.finalize_sweep(
        plan, transient.result_from_events(plan.operands, evt))
    _check(batch_mod.batches_identical(rescored, batch),
           "sweep result differs from its own kernel events")
    return {"design_points": len(space) // samples,
            "design_rows": len(batch),
            "kernel_rows": int(plan.operands.c.shape[0]),
            "samples": samples, "pallas_compiled": compiled,
            "plan_s": plan_s, "cold": cold, "warm": warm,
            "vs_ref": agreement}


def phase_anchors(backend: str = "auto") -> dict:
    """Table-1 anchors and the paper's selected design, nominal grid."""
    import numpy as np
    from repro.core import dse
    from repro.core.space import DesignSpace

    batch = dse.sweep(DesignSpace.paper_grid(), backend=backend)
    best = dse.best_design(batch)
    _check(best is not None, "no design meets the density target")
    selected = (f"{best.tech} / {best.scheme} @ {best.layers} -> "
                f"{best.density_gb_mm2:.2f} Gb/mm2, tRC {best.trc_ns:.2f} ns")
    _check(selected == SELECTED, f"selected {selected!r}, paper {SELECTED!r}")
    tech, scheme = batch.tech_col, batch.scheme_col
    layers = np.asarray(batch.layers)
    got = {}
    for t, s, n, trc_paper, dens_paper in ANCHORS:
        rows = [i for i in range(len(batch))
                if tech[i] == t and scheme[i] == s and int(layers[i]) == n]
        _check(len(rows) == 1, f"{t}/{s}@{n} not in the paper grid")
        trc = float(batch.trc_ns[rows[0]])
        dens = float(batch.density_gb_mm2[rows[0]])
        _check(abs(trc - trc_paper) / trc_paper < TRC_RTOL,
               f"{t}: tRC {trc:.3f} ns vs paper {trc_paper} ns")
        if dens_paper is not None:
            _check(abs(dens - dens_paper) / dens_paper < DENSITY_RTOL,
                   f"{t}: density {dens:.3f} vs paper {dens_paper}")
        got[t] = {"trc_ns": trc, "density_gb_mm2": dens}
    return {"selected": selected, "anchors": got}


def phase_service(backend: str = "auto", mc_samples: int = 1024) -> dict:
    """Concurrent clients in one window, then a memo hit on repeat."""
    from repro.core import dse
    from repro.core.batch import batches_identical
    from repro.core.space import DesignSpace
    from repro.serving.dse_service import DSEService

    queries = {
        "sweep": (DesignSpace.paper_grid(), {}),
        "yield": (DesignSpace.paper_targets().with_mc(samples=mc_samples,
                                                      key=1),
                  {"kind": "yield", "spec": {"margin_mv": 5.0}}),
        "replica": (DesignSpace.paper_grid().with_replica(), {}),
    }
    svc = DSEService(backend=backend)     # no dispatcher: flush() serves
    barrier = threading.Barrier(len(queries))
    futures = {}

    def client(name):
        space, kw = queries[name]
        barrier.wait()
        futures[name] = svc.submit(space, **kw)

    threads = [threading.Thread(target=client, args=(n,)) for n in queries]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t0 = time.perf_counter()
    svc.flush()
    window_s = time.perf_counter() - t0
    first = svc.stats()
    _check(first["windows"] == 1, f"{first['windows']} windows, expected 1")
    answers = {n: f.result(timeout=0) for n, f in futures.items()}
    for name, (space, _) in queries.items():
        _check(batches_identical(answers[name].batch,
                                 dse.sweep(space, backend=backend)),
               f"service answer {name!r} differs from a direct dse.sweep")
    _check(answers["yield"].summary is not None
           and "yield_frac" in answers["yield"].summary.corners,
           "yield query returned no yield summary")

    again = svc.submit(queries["sweep"][0])
    svc.flush()
    repeat = again.result(timeout=0)
    final = svc.stats()
    _check(repeat.memo_hit, "repeated query was not a memo hit")
    _check(final["dispatches"] == first["dispatches"],
           "repeated query dispatched again")
    _check(batches_identical(repeat.batch, answers["sweep"].batch),
           "memo hit returned a different batch")
    return {"clients": len(queries), "windows": final["windows"],
            "dispatches": final["dispatches"],
            "rows_dispatched": final["rows"]["dispatched"],
            "memo_hits": final["memo"]["hits"], "window_s": window_s}


def phase_sharded(samples: int = STUDY_SAMPLES, n_dev: int = 4,
                  backend: str = "auto") -> dict:
    """Sharded sweep and Pareto mask vs single-device, bit for bit."""
    import jax
    import numpy as np
    from repro.core import dse, transient
    from repro.core.batch import batches_identical
    from repro.launch import shard
    from repro.launch.mesh import make_sweep_mesh

    mesh = make_sweep_mesh(n_dev)
    devices = set(mesh.devices.flat)
    _check(len(devices) == n_dev, f"mesh holds {len(devices)} devices")
    space = study_space(samples)

    single, t_single = _timed_sweep(space, backend=backend)
    sharded, t_sharded = _timed_sweep(space, backend=backend, sharding=mesh)
    _check(batches_identical(sharded, single),
           "sharded sweep differs from the single-device sweep")

    # slabs land on every device of the mesh: the engine's per-device
    # output shards, each bit-identical to the same rows on one device
    plan = dse.plan_sweep(space)
    core = list(plan.operands[:6])
    b = core[0].shape[0]
    chunk = transient.DEFAULT_B_CHUNK
    target = shard._dispatch_target(b, n_dev, chunk)
    padded = [shard.put_global(x, shard.sweep_sharding(mesh))
              for x in transient._pad_operands(core, target - b)]
    evt_sh, _ = shard._sharded_engine(mesh, backend, chunk)(*padded)
    slab_devices = {s.device for s in evt_sh.addressable_shards}
    _check(slab_devices == devices,
           f"event slabs on {len(slab_devices)} of {n_dev} devices")
    evt_one = np.asarray(transient.row_cycle_events(plan.operands,
                                                    backend=backend))
    _check(np.array_equal(np.asarray(evt_sh)[:b], evt_one, equal_nan=True),
           "sharded kernel events differ from single-device events")

    t0 = time.perf_counter()
    mask = np.asarray(dse.pareto_mask(single))
    mask_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    mask_sh = np.asarray(dse.pareto_mask(single, sharding=mesh))
    mask_sh_s = time.perf_counter() - t0
    _check(np.array_equal(mask, mask_sh),
           "sharded Pareto mask differs from the single-device mask")
    return {"devices": len(devices),
            "device_kinds": sorted({d.device_kind for d in devices}),
            "design_rows": len(single), "kernel_rows": int(b),
            "single": t_single, "sharded": t_sharded,
            "pareto_points": int(mask.sum()), "pareto_single_s": mask_s,
            "pareto_sharded_s": mask_sh_s,
            "jax_devices": jax.device_count()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep + Pareto path")
    args = ap.parse_args(argv)

    try:
        info = phase_device(args.chips)
    except Exception as e:
        print(f"chip_smoke: FAIL device: {e}", file=sys.stderr)
        return 1
    log("device", **info)

    from repro.runtime.compile_cache import enable_compile_cache
    log("compile_cache", dir=enable_compile_cache())
    start = compile_totals()
    t_start = time.perf_counter()
    phases = ([("sharded", lambda: phase_sharded(n_dev=args.chips))]
              if args.chips > 1 else
              [("sweep", phase_sweep), ("anchors", phase_anchors),
               ("service", phase_service)])
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            out = run()
        except Exception as e:
            traceback.print_exc()
            print(f"chip_smoke: FAIL {name}: {e}", file=sys.stderr)
            return 1
        log(name, phase_s=time.perf_counter() - t0, **out)
    end = compile_totals()
    log("total", wall_s=time.perf_counter() - t_start,
        compile_s=end["seconds"] - start["seconds"],
        programs=end["programs"] - start["programs"],
        cache_hits=end["cache_loads"] - start["cache_loads"])
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
