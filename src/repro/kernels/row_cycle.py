"""Pallas TPU kernel: fused ACT/RESTORE/PRE row-cycle transient engine.

The phased engine (`rc_transient.rc_multistep_pallas` called three times from
`core.transient`) materializes a full (T, B, N) waveform per phase in HBM and
then scans it on the host side for the threshold crossings (90% signal
development, 95% restore, 5 mV equalization).  For the DSE — thousands of
(tech x scheme x layers) design points — those traces are pure waste: the
sweep only consumes O(B) event times and end-state voltages.

This kernel runs the *whole* row cycle in one `pallas_call`:

  - each design point carries its own phase state machine
    (0=ACT, 1=RESTORE, 2=PRE, 3=DONE) and a step-in-phase counter, so
    points cross thresholds and switch phases independently;
  - the WL ramp is evaluated analytically from the per-point WL tau
    (no (T,) ramp table, no gather);
  - crossings are detected in-VMEM right after each implicit-Euler step;
  - a `while_loop` exits as soon as every point in the block is DONE,
    so the typical step count is the sum of the *actual* phase durations,
    not the sum of the worst-case phase windows;
  - HBM traffic is one read of the netlist and one write of the O(B)
    events — independent of the number of time steps.

Phase semantics replicate `core.transient.simulate_row_cycle` (the phased
reference) step-for-step, so event times agree to within one dt.

Layout: the batch lies on the lanes and sublanes of full (8, 128) f32
tiles.  The wrapper pads B with inactive rows to whole blocks and lays each
(B, w) operand out as (w, B/128, 128): row r sits at sublane r // 128 and
lane r % 128, and `ref[i]` in the kernel is node (or parameter column) i of
a whole block as one (S_blk, 128) tile.  Every per-row operation then fills
each vreg it touches, where a (B_blk, 1) column would use one lane in 128.

Grid:      (ceil(B / block rows),)  — batch is the only blocked axis.  A
           block is `block_rows(B)` rows: B rounded up to whole 128-lane
           sublanes, at most DEFAULT_B_BLK (8 sublanes), so a
           2,048-row launch runs two (8, 128) blocks and a 64-row batch
           one (1, 128) tile.
Outputs:   events (B, 4) = [t_dev_ns, dv_sense_v, t_restore_dur_ns,
           t_pre_ns], v_end (B, N), and the `while_loop`'s trip count of
           each block (the steps it ran until its slowest row was DONE),
           transposed back from the kernel's (w, B/128, 128) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import RowCycleOut

LANES = 128
DEFAULT_B_BLK = 1024    # 8 sublanes x 128 lanes: one f32 vreg per quantity

# params (B, 6) column layout
PAR_TAU_WL = 0      # WL driver RC time constant [ns]
PAR_THR_REL = 1     # ACT threshold: v[0] - vpre >= thr_rel  [V]
PAR_VDD = 2         # restore rail (SA drives sense node here) [V]
PAR_VPRE = 3        # precharge / equalize target [V]
PAR_ACTIVE = 4      # 1.0 = live design point, 0.0 = padding (starts DONE)
PAR_ROLE = 5        # 0 = standalone fixed timing, 1 = replica bitline
                    # (fires row+1's SA enable, then DONE), 2 = main row
                    # closed by the replica at row-1.  A legacy (B, 5)
                    # params array is accepted: role defaults to 0.
N_PARAMS = 6

# events (B, 4) column layout
EVT_T_DEV = 0       # ACT: time to 90% signal development [ns]
EVT_DV_SENSE = 1    # developed signal at SA enable [V]
EVT_T_RES = 2       # RESTORE: duration to 95% VDD in the cell [ns]
EVT_T_PRE = 3       # PRE: duration to 5 mV equalization [ns]
N_EVENTS = 4

RESTORE_FRAC = 0.95     # cell restored when v_cell >= RESTORE_FRAC * VDD
EQUALIZE_TOL_V = 5e-3   # BL equalized when max |v - vpre| <= 5 mV

# PAR_ROLE values (float-coded in the params array)
ROLE_STANDALONE = 0.0
ROLE_REPLICA = 1.0
ROLE_MAIN = 2.0


def block_rows(b: int, b_blk: int = DEFAULT_B_BLK) -> int:
    """Rows of one batch block of a b-row launch: b rounded up to whole
    128-lane sublanes, at most `b_blk`."""
    return min(b_blk, pl.cdiv(b, LANES) * LANES)


def _row_cycle_kernel(c_ref, g_ref, gcr_ref, gcp_ref, v0_ref, par_ref,
                      evt_ref, vend_ref, steps_ref, *, n_act: int, n_res: int,
                      n_pre: int, dt: float):
    """One batch-block: phase state machine until every point is DONE.

    Mosaic lowers neither rank-1 vectors, gathers, scatters nor stacked
    booleans, so every per-row quantity is one (S_blk, 128) tile of the
    block's rows: the ladder state is a tuple of N node tiles, the phase
    and counter are int32 tiles, and the four event tiles are stored once
    at the end.  The block's trip count is stored in every lane of an
    int32 tile.
    """
    n = c_ref.shape[0]
    tile = c_ref.shape[1:]                                 # (S_blk, 128)
    cdt = [c_ref[i] / dt * 1e-3 for i in range(n)]        # fF/ns -> mS
    g_br = [g_ref[i] for i in range(n - 1)]
    gc_res = [gcr_ref[i] for i in range(n)]
    gc_pre = [gcp_ref[i] for i in range(n)]
    tau = jnp.maximum(par_ref[PAR_TAU_WL], 1e-3)
    thr_rel = par_ref[PAR_THR_REL]
    vdd = par_ref[PAR_VDD]
    vpre = par_ref[PAR_VPRE]
    active = par_ref[PAR_ACTIVE] > 0.5
    role = (par_ref[PAR_ROLE] if par_ref.shape[0] > PAR_ROLE
            else jnp.zeros_like(thr_rel))   # static: role column presence
    is_rep = jnp.abs(role - 1.0) < 0.5
    is_main = role > 1.5
    t_total = n_act + n_res + n_pre
    nan = jnp.float32(jnp.nan)

    def cond(state):
        t, phase = state[0], state[1]
        return jnp.logical_and(t < t_total, jnp.min(phase) < 3)

    def body(state):
        t, phase, tin, v, evt = state
        in_act = phase == 0
        in_res = phase == 1
        in_pre = phase == 2
        done = phase >= 3

        # WL ramp, analytic (matches transient.wl_ramp): x = 1 - e^{-t/tau}
        t_ns = (tin.astype(jnp.float32) + 1.0) * dt
        e = jnp.exp(-t_ns / tau)
        s = jnp.where(in_act, 1.0 - e,
                      jnp.where(in_res, 1.0, jnp.where(in_pre, e, 0.0)))

        # tridiagonal A = C/dt + G(s) + clamp, access branch scaled by s;
        # Thomas forward sweep (same operation order as ref._thomas_small)
        g = g_br[:n - 2] + [g_br[n - 2] * s]
        cp, dp = [], []
        for i in range(n):
            gc = jnp.where(in_res, gc_res[i],
                           jnp.where(in_pre, gc_pre[i], 0.0))
            gcv = jnp.where(in_res, gc_res[i] * vdd,
                            jnp.where(in_pre, gc_pre[i] * vpre, 0.0))
            lo = g[i - 1] if i > 0 else 0.0
            hi = g[i] if i < n - 1 else 0.0
            diag = cdt[i] + lo + hi + gc
            rhs = cdt[i] * v[i] + gcv
            if i == 0:
                cp.append(-hi / diag)
                dp.append(rhs / diag)
            else:
                denom = diag + lo * cp[i - 1]
                cp.append(-hi / denom)
                dp.append((rhs + lo * dp[i - 1]) / denom)
        x = [None] * n
        x[n - 1] = dp[n - 1]
        for i in range(n - 2, -1, -1):
            x[i] = dp[i] - cp[i] * x[i + 1]
        v_next = tuple(jnp.where(done, v[i], x[i]) for i in range(n))

        # threshold crossings on the fresh state, as int32 tiles.  A main
        # row's ACT crossing is the crossing of the replica at row-1, the
        # lane before it: [replica, main] pairs are even-aligned, so a pair
        # never straddles two sublanes, lane 0 of every sublane is a replica
        # and the wrapped value is unused.  Pairs run ACT in lockstep.
        dv = v_next[0] - vpre
        cross_own = (dv >= thr_rel).astype(jnp.int32)
        cross_prev = pltpu.roll(cross_own, 1, 1)
        cross_act = jnp.where(is_main, cross_prev, cross_own)
        cross_res = (v_next[n - 1] >= RESTORE_FRAC * vdd).astype(jnp.int32)
        dev = jnp.abs(v_next[0] - vpre)
        for i in range(1, n - 1):
            dev = jnp.maximum(dev, jnp.abs(v_next[i] - vpre))
        cross_pre = (dev <= EQUALIZE_TOL_V).astype(jnp.int32)

        tin1 = tin + 1
        crossed = jnp.where(in_act, cross_act,
                            jnp.where(in_res, cross_res, cross_pre)) > 0
        cap = jnp.where(in_act, n_act, jnp.where(in_res, n_res, n_pre))
        advance = jnp.logical_and(~done,
                                  jnp.logical_or(crossed, tin1 >= cap))
        # first-crossing time: (idx+1)*dt, or NaN if the phase timed out
        t_evt = jnp.where(crossed, tin1.astype(jnp.float32) * dt, nan)

        rec = lambda ph: jnp.logical_and(advance, phase == ph)
        t_dev, dv_sense, t_res, t_pre = evt
        evt = (jnp.where(rec(0), t_evt, t_dev),
               jnp.where(rec(0), dv, dv_sense),
               jnp.where(rec(1), t_evt, t_res),
               jnp.where(rec(2), t_evt, t_pre))

        # replica rows are ACT-only: they jump straight to DONE
        phase_inc = jnp.where(is_rep, 3, 1)
        phase = jnp.where(advance, phase + phase_inc, phase)
        tin = jnp.where(advance, 0, jnp.where(done, tin, tin1))
        return t + 1, phase, tin, v_next, evt

    zero = jnp.zeros(tile, jnp.float32)
    state = (jnp.int32(0), jnp.where(active, 0, 3).astype(jnp.int32),
             jnp.zeros(tile, jnp.int32),
             tuple(v0_ref[i] for i in range(n)), (zero,) * N_EVENTS)
    t_fin, _, _, v_fin, evt_fin = jax.lax.while_loop(cond, body, state)
    steps_ref[...] = jnp.full(tile, t_fin, jnp.int32)
    for k in range(N_EVENTS):
        evt_ref[k] = evt_fin[k]
    for i in range(n):
        vend_ref[i] = v_fin[i]


def row_cycle_fused_pallas(c: jnp.ndarray, g_branch: jnp.ndarray,
                           gc_res: jnp.ndarray, gc_pre: jnp.ndarray,
                           v0: jnp.ndarray, params: jnp.ndarray,
                           dt: float, n_act: int, n_res: int, n_pre: int,
                           *, b_blk: int = DEFAULT_B_BLK,
                           interpret: bool = True):
    """Pallas-backed equivalent of `ref.row_cycle_fused_ref`.

    Takes (B, w) operands and returns a `RowCycleOut`: (events, v_end)
    with shapes ((B, 4), (B, N)), and `block_steps` (ceil(B / rows),)
    int32, each block's trip count, for blocks of `block_rows(B, b_blk)`
    rows.  `b_blk` is a multiple of 128; only tests set it, to force
    several blocks on a small batch.
    """
    if b_blk <= 0 or b_blk % LANES:
        raise ValueError(f"b_blk={b_blk} must be a positive multiple of "
                         f"{LANES}")
    b, n = c.shape
    blk = block_rows(b, b_blk)
    n_blocks = pl.cdiv(b, blk)
    rows = n_blocks * blk

    def tiles(x, fill):
        """(B, w) -> (w, rows/128, 128), padded with `fill`."""
        x = jnp.pad(x.T, ((0, 0), (0, rows - b)), constant_values=fill)
        return x.reshape(x.shape[0], rows // LANES, LANES)

    # padded rows get active=0 -> they start DONE and never step
    c, g_branch, gc_res, gc_pre, v0 = (
        tiles(x, 1.0) for x in (c, g_branch, gc_res, gc_pre, v0))
    params = tiles(params, 0.0)

    kernel = functools.partial(_row_cycle_kernel, n_act=n_act, n_res=n_res,
                               n_pre=n_pre, dt=dt)
    s_blk = blk // LANES
    bspec = lambda w: pl.BlockSpec((w, s_blk, LANES), lambda i: (0, i, 0))
    tshape = lambda w, dtype: jax.ShapeDtypeStruct((w, rows // LANES, LANES),
                                                   dtype)
    events, v_end, steps = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[bspec(n), bspec(n - 1), bspec(n), bspec(n), bspec(n),
                  bspec(params.shape[0])],  # (B, 5) legacy or (B, 6)
        out_specs=[bspec(N_EVENTS), bspec(n),
                   pl.BlockSpec((s_blk, LANES), lambda i: (i, 0))],
        out_shape=[tshape(N_EVENTS, jnp.float32), tshape(n, c.dtype),
                   jax.ShapeDtypeStruct((rows // LANES, LANES), jnp.int32)],
        interpret=interpret,
        name="row_cycle_fused",
    )(c, g_branch, gc_res, gc_pre, v0, params)
    untile = lambda x: x.reshape(x.shape[0], rows).T[:b]
    return RowCycleOut(untile(events), untile(v_end),
                       steps.reshape(n_blocks, blk)[:, 0])
