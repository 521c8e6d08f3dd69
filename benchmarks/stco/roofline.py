"""Work of the fused row-cycle kernel, counted from the design data.

Only useful steps count: each kernel row needs the implicit-Euler steps
that take it to DONE, read from its own event times (the full phase
window where an event is NaN, i.e. the phase timed out).  A replica row
runs ACT only, in lockstep with its main row.  Padding rows, and steps a
block spends waiting for its slowest row, are not work.

One step of one row on the 6-node ladder (N = 6 nodes, N - 1 branches),
in f32 operations, following the plain per-step update:
"""

from __future__ import annotations

import numpy as np

N_NODES = 6
OPS_PER_STEP_BY_PART = {
    "step time (tin + 1) * dt": 2,
    "WL ramp exp(-t / tau), 1 - e": 4,
    "access branch g * s": 1,
    "clamp conductance and source terms": 2 * N_NODES,
    "diagonal C/dt + g_lo + g_hi + gc": 3 * N_NODES,
    "off-diagonals -g": 2 * (N_NODES - 1),
    "right-hand side C/dt * v + gc * target": 2 * N_NODES,
    "Thomas forward sweep": 2 + 6 * (N_NODES - 1),
    "Thomas back substitution": 2 * (N_NODES - 1),
    "crossing tests (ACT, restore, equalize)": 2 + 2 + 2 * (N_NODES - 1) + (N_NODES - 2) + 1,
    "event time tin1 * dt": 1,
}
OPS_PER_STEP = sum(OPS_PER_STEP_BY_PART.values())
# operands read per kernel row: c, gc_res, gc_pre, v0 (N each), g (N - 1),
# params (6); event row written: 4 f32
BYTES_PER_ROW = 4 * (4 * N_NODES + (N_NODES - 1) + 6) + 4 * 4


def row_steps(config, t_fire, rest) -> tuple[np.ndarray, np.ndarray]:
    """Useful (ACT, RESTORE + PRE) steps of main rows from t_fire and
    t_res + t_pre (ns)."""
    rc = config["row_cycle"]
    dt = float(rc["dt_ns"])
    act = np.where(np.isfinite(t_fire), np.rint(t_fire / dt), rc["act_steps"])
    tail = np.where(np.isfinite(rest), np.rint(rest / dt),
                    rc["restore_steps"] + rc["pre_steps"])
    return act, tail


def study_work(config, spec, batch) -> tuple[float, float]:
    """(f32 operations, HBM bytes) the kernel needs for one study."""
    t_fire = np.asarray(batch.t_fire_ns, np.float64)
    overhead = np.asarray([config["techs"][t]["t_overhead_ns"] for t in batch.tech_names],
                          np.float64)[np.asarray(batch.tech_idx)]
    rest = (np.asarray(batch.trc_ns, np.float64) - overhead
            - np.asarray(batch.t_sense_ns, np.float64))
    act, tail = row_steps(config, t_fire, rest)
    replica = bool(spec.get("replica"))
    steps = act.sum() * (2 if replica else 1) + tail.sum()
    rows = len(t_fire) * (2 if replica else 1)
    return float(steps * OPS_PER_STEP), float(rows * BYTES_PER_ROW)


def bound(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least seconds the chip could take, and which peak bounds it."""
    t_ops = ops / peak["flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_mem else (t_mem, "hbm_bytes")
