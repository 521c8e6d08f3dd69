"""Batched transient simulation of the full row cycle (the paper's Fig. 8).

Implicit-Euler on the sensing-path RC ladder with a behavioral BLSA, phased
exactly like a DRAM row cycle:

  ACT   : WL ramps up (access branch scale 0->1), cell shares charge with
          the BL network; the BLSA is enabled once the sense node has
          developed 90% of its asymptotic signal (+ latch regeneration).
  RESTORE: the latched BLSA drives the sense node to the full rail through
          its drive resistance, recharging the cell through the BL + access
          transistor until 95% of VDD is restored.
  PRE   : WL ramps down, equalizer clamps all BL nodes to VDD/2 until
          within 5 mV.

tRC = t_overhead + t(ACT+RESTORE) + t(PRE).

Two execution engines, same physics:

  fused (default)      — one `repro.kernels.ops.row_cycle_fused` call runs
          all three phases with in-kernel crossing detection and returns
          O(B) event times/voltages; no (T, B, N) trace ever exists.  This
          is what the DSE sweeps thousands of design points through, and
          `simulate_row_cycle_many` batches arbitrary (tech, scheme,
          layers) combos through ONE fused evaluation (VMEM-bounded by
          batch chunking).
  phased (traces=True) — three `rc_multistep` calls that materialize the
          per-phase waveforms for Fig. 8 plotting; also the reference the
          fused engine is regression-tested against (within one dt).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import obs
from . import calibration as cal
from . import contracts
from .calibration import TechCal
from .netlist import (Ladder, build_bl_ladder, build_ladder_lowered,
                      replica_ladder_arrays)
from ..kernels import ops
from ..kernels.row_cycle import ROLE_MAIN, ROLE_REPLICA
from .units import tau_ns

DT_NS = 0.02
T_ACT_NS = 16.0
T_RESTORE_NS = 20.0
T_PRE_NS = 10.0

N_ACT_STEPS = int(T_ACT_NS / DT_NS)
N_RESTORE_STEPS = int(T_RESTORE_NS / DT_NS)
N_PRE_STEPS = int(T_PRE_NS / DT_NS)

# default fused-engine chunk: bounds device memory for arbitrary DSE grids
DEFAULT_B_CHUNK = 2048


@dataclass(frozen=True)
class RowCycleResult:
    t_sense_ns: jnp.ndarray       # WL start -> SA latched
    t_restore_ns: jnp.ndarray     # WL start -> cell restored (tRAS analogue)
    t_precharge_ns: jnp.ndarray   # precharge duration (tRP analogue)
    trc_ns: jnp.ndarray           # total row cycle
    dv_sense_v: jnp.ndarray       # developed signal at SA enable
    traces: dict                  # phase -> (T, B, N) waveforms (phased only)
    t_fire_ns: jnp.ndarray | None = None  # SA-enable fire time (the ACT
    # first-crossing; replica-closed when the replica path is enabled)
    events: jnp.ndarray | None = None     # raw (B, 4) fused-engine event
    # columns BEFORE replica de-interleave — the exact engine output.
    # Carried so `dse.finalize_sweep` can re-derive every scored column
    # through the one jitted rollup+score program both the sequential
    # and sharded sweeps run (their bit-equivalence contract).


def _first_crossing_ns(trace_ok: jnp.ndarray, dt: float) -> jnp.ndarray:
    """Time of first True along axis 0 of (T, B); NaN if never crossed.

    A crossing on the very last step returns the finite T*dt — distinct
    from never-crossed (an older revision returned the phase window for
    both, silently aliasing a last-step crossing with a timeout).
    """
    any_ok = jnp.any(trace_ok, axis=0)
    idx = jnp.argmax(trace_ok, axis=0)
    return jnp.where(any_ok, (idx + 1) * dt, jnp.nan)


def wl_ramp(tech: TechCal, t_ns: jnp.ndarray, rising: bool = True) -> jnp.ndarray:
    """WL voltage profile (normalized 0..1): RC-limited driver."""
    tau = tau_ns(tech.r_wl_kohm, tech.c_wl_ff)
    x = 1.0 - jnp.exp(-t_ns / jnp.maximum(tau, 1e-3))
    return x if rising else 1.0 - x


def _regen_and_totals(tech_sa_tau, tech_overhead, t_dev, dv_sense,
                      t_res_dur, t_pre):
    """BLSA latch regeneration + phase roll-up (shared by both engines)."""
    vdd = cal.VDD_ARRAY
    t_regen = tech_sa_tau * jnp.log(
        jnp.maximum((vdd / 2.0) / jnp.maximum(dv_sense, 1e-4), 1.001))
    t_sense = t_dev + t_regen
    t_restore = t_sense + t_res_dur
    trc = tech_overhead + t_restore + t_pre
    return t_sense, t_restore, trc


class FusedOperands(NamedTuple):
    """Lowered operand arrays for one flat design-point batch.

    This is the canonical wire format between the DSE layer and the fused
    row-cycle engine: six (B, ...) kernel operands plus the two per-point
    roll-up vectors.  `dse.sweep` lowers a whole DesignSpace into ONE of
    these; `simulate_row_cycle_many` accepts it directly.
    """
    c: jnp.ndarray              # (B, N) node capacitances
    g: jnp.ndarray              # (B, N-1) branch conductances
    gc_res: jnp.ndarray         # (B, N) restore clamp conductances
    gc_pre: jnp.ndarray         # (B, N) precharge clamp conductances
    v0: jnp.ndarray             # (B, N) initial node voltages
    params: jnp.ndarray         # (B, 6) per-point kernel params
    #                             (incl. ACTIVE and ROLE columns)
    sa_tau_ns: jnp.ndarray      # (B,) BLSA regeneration time constants
    t_overhead_ns: jnp.ndarray  # (B,) command/decode overheads
    replica: bool = False       # True -> rows are interleaved
    #                             [replica, main] pairs (replica-closed
    #                             timing); B is twice the design-point count


def lower_operands(c, g, *, r_sa_drive_kohm, r_pre_kohm, store_v, tau_wl_ns,
                   active=None, role=None):
    """Lower ladder arrays + drive parameters to fused-kernel operands.

    Every parameter may be a scalar (one tech) or a (B,) array (the
    vectorized DSE path over mixed techs); `active=0` rows are padding /
    masked-out design points that the kernel starts in the DONE state.
    `role` selects the kernel's SA-enable timing mode per row (see
    `kernels.row_cycle.ROLE_*`; default standalone fixed timing).
    """
    b, n = c.shape
    vdd, vpre = cal.VDD_ARRAY, cal.VBL_PRE
    c = c.astype(jnp.float32)
    g = g.astype(jnp.float32)

    def vec(x):
        return jnp.broadcast_to(jnp.asarray(x, jnp.float32), (b,))

    zeros = jnp.zeros((b, n), jnp.float32)
    gc_res = zeros.at[:, 0].set(vec(1.0 / jnp.asarray(r_sa_drive_kohm)))
    gc_pre = zeros.at[:, : n - 1].set(
        vec(1.0 / jnp.asarray(r_pre_kohm))[:, None])
    store_v = vec(store_v)
    v0 = jnp.full((b, n), vpre, jnp.float32).at[:, n - 1].set(store_v)

    cbl = c[:, : n - 1].sum(-1)
    cs = c[:, n - 1]
    dv_inf = (store_v - vpre) * cs / (cs + cbl)
    params = jnp.stack([
        vec(tau_wl_ns),
        0.9 * dv_inf.astype(jnp.float32),
        jnp.full((b,), vdd, jnp.float32),
        jnp.full((b,), vpre, jnp.float32),
        jnp.ones((b,), jnp.float32) if active is None else vec(active),
        jnp.zeros((b,), jnp.float32) if role is None else vec(role),
    ], axis=1)
    return c, g, gc_res, gc_pre, v0, params


def _fused_operands(ladder: Ladder, tech: TechCal, store_v: float,
                    role=None):
    """Assemble the fused-engine operand arrays for one (tech, scheme)."""
    return lower_operands(
        ladder.c, ladder.g_branch,
        r_sa_drive_kohm=tech.r_sa_drive_kohm, r_pre_kohm=tech.r_pre_kohm,
        store_v=store_v, tau_wl_ns=tau_ns(tech.r_wl_kohm, tech.c_wl_ff),
        role=role)


def _interleave(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Row-interleave two equally-shaped batches: [a0, b0, a1, b1, ...]."""
    return jnp.stack([a, b], axis=1).reshape((-1,) + a.shape[1:])


def lower_design_operands(view, ladder_c=None, ladder_g=None,
                          par=None) -> FusedOperands:
    """Lower a whole design space view to ONE fused-engine operand batch.

    `view` follows the LoweredSpace protocol (`core.space`); ladder arrays
    / parasitics are rebuilt unless passed in.  Masked-out points
    (`view.valid == False`) become inactive kernel rows.

    Monte-Carlo spaces need no special handling here: the per-sample Vth
    draw is already folded into the access-transistor conductance by
    `parasitics.bl_parasitics_lowered`, so the sampled rows flow through
    the same single chunked fused dispatch as nominal design points.

    When `view.replica` is set, every design point lowers to TWO adjacent
    kernel rows — [replica, main] — with the replica's ladder derived from
    the SAME parasitics (so MC Vth draws perturb both), storage scaled by
    the tech's `replica_cells`, and role columns wiring the replica's ACT
    crossing to the main row's SA enable.  All batch boundaries downstream
    (B_ALIGN padding, chunking, Pallas blocks, device slabs) are even, so
    a pair is never split.
    """
    if ladder_c is None or ladder_g is None:
        ladder_c, ladder_g = build_ladder_lowered(view, par)
    replica = bool(getattr(view, "replica", False))
    b = ladder_c.shape[0]
    active = view.valid.astype(jnp.float32)
    sa_tau = jnp.broadcast_to(
        jnp.asarray(view.tech("sa_tau_ns"), jnp.float32), (b,))
    overhead = jnp.broadcast_to(
        jnp.asarray(view.tech("t_overhead_ns"), jnp.float32), (b,))
    tau_wl = tau_ns(view.tech("r_wl_kohm"), view.tech("c_wl_ff"))
    core = lower_operands(
        ladder_c, ladder_g,
        r_sa_drive_kohm=view.tech("r_sa_drive_kohm"),
        r_pre_kohm=view.tech("r_pre_kohm"),
        store_v=view.tech("writeback_eff") * cal.VDD_ARRAY,
        tau_wl_ns=tau_wl,
        active=active,
        role=ROLE_MAIN if replica else None)
    if replica:
        rep_c, rep_g = replica_ladder_arrays(
            ladder_c, ladder_g, view.tech("replica_cells"))
        rep = lower_operands(
            rep_c, rep_g,
            r_sa_drive_kohm=view.tech("r_sa_drive_kohm"),
            r_pre_kohm=view.tech("r_pre_kohm"),
            store_v=view.tech("replica_store_frac") * cal.VDD_ARRAY,
            tau_wl_ns=tau_wl,
            active=active,
            role=ROLE_REPLICA)
        core = tuple(_interleave(r, m) for r, m in zip(rep, core))
        sa_tau = _interleave(sa_tau, sa_tau)
        overhead = _interleave(overhead, overhead)
    operands = FusedOperands(
        *core, sa_tau_ns=sa_tau, t_overhead_ns=overhead, replica=replica)
    contracts.check_operands(operands, where="transient.lower_design_operands")
    return operands


# Fused-engine batches are padded (with inactive design points) up to a
# multiple of this, so arbitrary small batches share one compiled shape —
# the while-loop engine's jit trace is the dominant one-off cost.
B_ALIGN = 64


def _pad_operands(operands, pad: int):
    """Append `pad` inactive design points (params[:, ACTIVE] = 0)."""
    if not pad:
        return list(operands)
    padf = lambda x, v: jnp.pad(x, ((0, pad), (0, 0)), constant_values=v)
    padded = [padf(x, 1.0) for x in operands[:5]]
    padded.append(padf(operands[5], 0.0))
    return padded


def validate_b_chunk(b_chunk: int) -> int:
    """Check a fused-engine chunk size; returns it as an int.

    Chunks are the caller's memory bound, so they must be honorable
    exactly: every dispatch is padded to a B_ALIGN multiple for compiled-
    shape sharing, and a `b_chunk` that is not itself a B_ALIGN multiple
    would force either an unaligned shape or a silently larger pad.
    """
    b_chunk = int(b_chunk)
    if b_chunk < B_ALIGN or b_chunk % B_ALIGN:
        raise ValueError(
            f"b_chunk={b_chunk} must be a positive multiple of B_ALIGN "
            f"({B_ALIGN}); smaller or unaligned chunks cannot be honored "
            "without exceeding the requested memory bound")
    return b_chunk


_pad_batch = jax.jit(_pad_operands, static_argnums=1)


@functools.partial(jax.jit, static_argnames=("rows",))
def _split_chunks(padded, rows: int):
    """Cut a padded batch into chunks of `rows`: one device program, with
    every chunk of every operand its own output (a tuple of 6-tuples), so
    no chunk is sliced on the host.  Keyed on the chunk count and rows,
    not on the rows given."""
    return tuple(tuple(x[lo:lo + rows] for x in padded)
                 for lo in range(0, padded[0].shape[0], rows))


@jax.jit
def _join_chunks(evts, v_ends, steps):
    """Every launch's events and v_end, joined, and the total of their
    per-block step counts (None where the seam gives none): one device
    program after the last launch."""
    total = None if steps is None else jnp.concatenate(steps).sum()
    return jnp.concatenate(evts), jnp.concatenate(v_ends), total


def _row_cycle_fused_chunked(operands, backend: str, b_chunk: int):
    """Feed (c, g, gc_res, gc_pre, v0, params) through the fused engine in
    fixed-size chunks so arbitrary sweep grids fit VMEM/HBM.

    Every call is padded with inactive design points to a B_ALIGN multiple
    no larger than `b_chunk` (which must itself be a B_ALIGN multiple), so
    the kernel's programs are shared by every batch size and no launch
    exceeds the caller's memory bound.  A batch of more than one chunk is
    cut in one program (`_split_chunks`, keyed on the chunk count), each
    chunk goes to the kernel in its own host-side call, and one program
    joins the outputs (`_join_chunks`); a batch that is one whole chunk
    goes to the kernel as it is.

    Runs as the `engine.dispatch` span (`repro.obs`), which counts the
    kernel `launches`, the `rows` given, the `rows_padded` after chunk and
    block padding, the `chunk_splits` (split programs), and the kernel's
    own `block_steps`, summed on the device after the last launch.
    Nothing here reads a device value; the runtime may still hold an
    enqueue while earlier launches run.
    """
    b_chunk = validate_b_chunk(b_chunk)
    b = operands[0].shape[0]
    rows = min(-(-b // B_ALIGN) * B_ALIGN, b_chunk)
    pad = (-b) % rows
    with obs.span("engine.dispatch"):
        padded = tuple(_pad_batch(tuple(operands), pad) if pad else operands)
        if b + pad == rows:
            chunks = [padded]
        else:
            chunks = _split_chunks(padded, rows)
            obs.count("chunk_splits", 1)
        outs = [ops.row_cycle_fused(*chunk, DT_NS, N_ACT_STEPS,
                                    N_RESTORE_STEPS, N_PRE_STEPS,
                                    backend=backend)
                for chunk in chunks]
        # a seam replaced by a plain (events, v_end) pair reports no count
        steps = tuple(getattr(o, "block_steps", None) for o in outs)
        evt, v_end, total = _join_chunks(tuple(o[0] for o in outs),
                                         tuple(o[1] for o in outs),
                                         None if None in steps else steps)
        if pad:
            evt, v_end = evt[:b], v_end[:b]
        block = ops.row_cycle_block_rows(rows, backend)
        obs.count("launches", len(outs))
        obs.count("rows", b)
        obs.count("rows_padded", len(outs) * -(-rows // block) * block)
        if total is not None:
            obs.count("block_steps", total)
    return evt, v_end


def simulate_row_cycle(tech: TechCal, scheme: str, layers,
                       store_v: float | None = None,
                       backend: str = "auto",
                       traces: bool = False,
                       b_chunk: int = DEFAULT_B_CHUNK,
                       replica: bool = False) -> RowCycleResult:
    """Simulate ACT/RESTORE/PRE on the ladder; batched over `layers`.

    Default path is the fused trace-free engine; pass ``traces=True`` to run
    the phased three-call engine and get the full (T, B, N) waveforms
    (Fig. 8 plotting).  ``replica=True`` closes the SA-enable timing with a
    replica bitline (scaled by ``tech.replica_cells``) instead of the fixed
    own-90% crossing.
    """
    if traces:
        return simulate_row_cycle_phased(tech, scheme, layers,
                                         store_v=store_v, backend=backend,
                                         replica=replica)
    ladder = build_bl_ladder(tech, scheme, layers)
    if store_v is None:
        store_v = tech.writeback_eff * cal.VDD_ARRAY
    if replica:
        main = _fused_operands(ladder, tech, store_v, role=ROLE_MAIN)
        rep_c, rep_g = replica_ladder_arrays(ladder.c, ladder.g_branch,
                                             tech.replica_cells)
        rep = lower_operands(
            rep_c, rep_g,
            r_sa_drive_kohm=tech.r_sa_drive_kohm,
            r_pre_kohm=tech.r_pre_kohm,
            store_v=tech.replica_store_frac * cal.VDD_ARRAY,
            tau_wl_ns=tau_ns(tech.r_wl_kohm, tech.c_wl_ff),
            role=ROLE_REPLICA)
        operands = tuple(_interleave(r, m) for r, m in zip(rep, main))
        evt, _ = _row_cycle_fused_chunked(operands, backend, b_chunk)
        evt = evt[1::2]
    else:
        operands = _fused_operands(ladder, tech, store_v)
        evt, _ = _row_cycle_fused_chunked(operands, backend, b_chunk)
    t_dev, dv_sense, t_res_dur, t_pre = (evt[:, 0], evt[:, 1],
                                         evt[:, 2], evt[:, 3])
    t_sense, t_restore, trc = _regen_and_totals(
        tech.sa_tau_ns, tech.t_overhead_ns, t_dev, dv_sense, t_res_dur, t_pre)
    return RowCycleResult(
        t_sense_ns=t_sense, t_restore_ns=t_restore, t_precharge_ns=t_pre,
        trc_ns=trc, dv_sense_v=dv_sense, traces={}, t_fire_ns=t_dev)


def result_from_events(operands: FusedOperands,
                       evt: jnp.ndarray) -> RowCycleResult:
    """Roll fused-engine event columns up into a `RowCycleResult`.

    Shared by the sequential path below and the sharded driver
    (`launch.shard`), so the two can never diverge in how events map to
    result fields — a precondition of their bit-equivalence contract.

    Replica-interleaved batches are de-interleaved here: the replica rows
    (even indices) only exist to time the main rows' SA enable, so the
    result covers the main rows (odd indices) and has the design-point
    length the caller handed to `lower_design_operands`.
    """
    raw = evt
    sa_tau, overhead = operands.sa_tau_ns, operands.t_overhead_ns
    if getattr(operands, "replica", False):
        evt = evt[1::2]
        sa_tau = sa_tau[1::2]
        overhead = overhead[1::2]
    t_sense, t_restore, trc = _regen_and_totals(
        sa_tau, overhead, evt[:, 0], evt[:, 1], evt[:, 2], evt[:, 3])
    return RowCycleResult(
        t_sense_ns=t_sense, t_restore_ns=t_restore,
        t_precharge_ns=evt[:, 3], trc_ns=trc,
        dv_sense_v=evt[:, 1], traces={}, t_fire_ns=evt[:, 0], events=raw)


def row_cycle_events(operands: FusedOperands, backend: str = "auto",
                     b_chunk: int = DEFAULT_B_CHUNK) -> jnp.ndarray:
    """Raw fused-engine event columns for a lowered operand batch -> (B, 4).

    The pre-rollup view of `simulate_row_cycle_lowered`: one chunked pass
    through the fused engine, no `_regen_and_totals`, no replica
    de-interleave.  This is the serving layer's packing seam — many
    requests' operand batches can be concatenated, dispatched once, and
    the event rows sliced back per request before each request's own
    `result_from_events` rollup (which is where replica pairs collapse).
    """
    evt, _ = _row_cycle_fused_chunked(operands[:6], backend, b_chunk)
    return evt


def simulate_row_cycle_lowered(operands: FusedOperands,
                               backend: str = "auto",
                               b_chunk: int = DEFAULT_B_CHUNK) -> RowCycleResult:
    """Fused row-cycle over an already-lowered flat operand batch.

    This is the array-native entry point of the engine: the DSE sweep
    lowers its whole (tech x scheme x layers [x corners]) space to ONE
    `FusedOperands` and gets ONE trace-free `RowCycleResult` back, with no
    per-combo Python loop anywhere.
    """
    evt, _ = _row_cycle_fused_chunked(operands[:6], backend, b_chunk)
    return result_from_events(operands, evt)


def simulate_row_cycle_many(entries, backend: str = "auto",
                            b_chunk: int = DEFAULT_B_CHUNK):
    """Fused row-cycle over many (tech, scheme, layers) combos at once.

    `entries` is either a sequence of (TechCal, scheme, layers-array)
    tuples, or an already-lowered `FusedOperands` batch (from
    `lower_design_operands`), which is dispatched directly.  All design
    points are flattened into ONE batch through the fused engine (chunked
    to `b_chunk`), instead of one transient call per combo — this is what
    makes `dse.sweep` a single vectorized evaluation.  Returns one
    trace-free RowCycleResult per entry (or one flat result for a lowered
    batch).
    """
    if isinstance(entries, FusedOperands):
        return simulate_row_cycle_lowered(entries, backend, b_chunk)

    per_entry = []
    cs, gs, gcrs, gcps, v0s, pars = [], [], [], [], [], []
    sa_taus, overheads = [], []
    for tech, scheme, layers in entries:
        ladder = build_bl_ladder(tech, scheme, layers)
        store_v = tech.writeback_eff * cal.VDD_ARRAY
        c, g, gc_res, gc_pre, v0, params = _fused_operands(
            ladder, tech, store_v)
        b = c.shape[0]
        per_entry.append(b)
        cs.append(c); gs.append(g); gcrs.append(gc_res); gcps.append(gc_pre)
        v0s.append(v0); pars.append(params)
        sa_taus.append(jnp.full((b,), tech.sa_tau_ns, jnp.float32))
        overheads.append(jnp.full((b,), tech.t_overhead_ns, jnp.float32))

    operands = FusedOperands(
        *(jnp.concatenate(xs, axis=0)
          for xs in (cs, gs, gcrs, gcps, v0s, pars)),
        sa_tau_ns=jnp.concatenate(sa_taus),
        t_overhead_ns=jnp.concatenate(overheads))
    flat = simulate_row_cycle_lowered(operands, backend, b_chunk)

    results, lo = [], 0
    for b in per_entry:
        sl = slice(lo, lo + b)
        results.append(RowCycleResult(
            t_sense_ns=flat.t_sense_ns[sl], t_restore_ns=flat.t_restore_ns[sl],
            t_precharge_ns=flat.t_precharge_ns[sl], trc_ns=flat.trc_ns[sl],
            dv_sense_v=flat.dv_sense_v[sl], traces={}))
        lo += b
    return results


def simulate_row_cycle_phased(tech: TechCal, scheme: str, layers,
                              store_v: float | None = None,
                              backend: str = "ref",
                              replica: bool = False) -> RowCycleResult:
    """Phased three-call engine: materializes full (T, B, N) waveforms.

    This is the Fig. 8 plotting path and the reference the fused engine is
    validated against (event times within one dt) — including the
    replica-closed timing mode, where the SA enable fires on the replica
    bitline's own first crossing instead of the main array's.
    """
    ladder = build_bl_ladder(tech, scheme, layers)
    b, n = ladder.c.shape
    vdd, vpre = cal.VDD_ARRAY, cal.VBL_PRE
    if store_v is None:
        store_v = tech.writeback_eff * vdd

    c = ladder.c.astype(jnp.float32)
    g = ladder.g_branch.astype(jnp.float32)
    zero_clamp = jnp.zeros((b, n), jnp.float32)

    # ---------------- ACT: WL up, charge share --------------------------
    n_act = N_ACT_STEPS
    t_grid = (jnp.arange(n_act) + 1) * DT_NS
    ramp_up = wl_ramp(tech, t_grid).astype(jnp.float32)
    v0 = jnp.full((b, n), vpre, jnp.float32).at[:, n - 1].set(store_v)
    trace_act = ops.rc_multistep(c, g, zero_clamp, zero_clamp, v0,
                                 ramp_up, DT_NS, backend=backend)

    if replica:
        # replica column: same ladder with the storage end scaled by the
        # replica cell count; its OWN 90% crossing fires the SA enable.
        rep_c, rep_g = replica_ladder_arrays(ladder.c, ladder.g_branch,
                                             tech.replica_cells)
        rep_c = rep_c.astype(jnp.float32)
        rep_g = rep_g.astype(jnp.float32)
        rep_store = tech.replica_store_frac * vdd
        rep_v0 = jnp.full((b, n), vpre, jnp.float32).at[:, n - 1].set(
            rep_store)
        trace_rep = ops.rc_multistep(rep_c, rep_g, zero_clamp, zero_clamp,
                                     rep_v0, ramp_up, DT_NS, backend=backend)
        rep_cbl = rep_c[:, :n - 1].sum(-1)
        rep_cs = rep_c[:, n - 1]
        rep_dv_inf = (rep_store - vpre) * rep_cs / (rep_cs + rep_cbl)
        crossed = (trace_rep[:, :, 0] - vpre
                   >= 0.9 * rep_dv_inf[None, :].astype(jnp.float32))
    else:
        cbl = ladder.c[:, :n - 1].sum(-1)
        cs = ladder.c[:, n - 1]
        dv_inf = (store_v - vpre) * cs / (cs + cbl)
        crossed = (trace_act[:, :, 0] - vpre
                   >= 0.9 * dv_inf[None, :].astype(jnp.float32))
    t_dev = _first_crossing_ns(crossed, DT_NS)

    # developed signal actually available at SA enable; a NaN (never
    # crossed) t_dev keeps the downstream phases well-defined by indexing
    # the end of the ACT window — the NaN still propagates into
    # t_sense/trc through `_regen_and_totals`.
    t_dev_idx = jnp.where(jnp.isnan(t_dev), T_ACT_NS, t_dev)
    idx_dev = jnp.clip((t_dev_idx / DT_NS).astype(jnp.int32) - 1, 0,
                       n_act - 1)
    dv_sense = trace_act[idx_dev, jnp.arange(b), 0] - vpre

    # ---------------- RESTORE: SA drives the rail -----------------------
    n_res = N_RESTORE_STEPS
    # state at SA enable: take the trace at t_dev (per design point)
    v_at_dev = trace_act[idx_dev, jnp.arange(b), :]
    g_clamp_res = zero_clamp.at[:, 0].set(1.0 / tech.r_sa_drive_kohm)
    v_clamp_res = jnp.full((b, n), vdd, jnp.float32)
    ramp_on = jnp.ones((n_res,), jnp.float32)
    trace_res = ops.rc_multistep(c, g, g_clamp_res, v_clamp_res, v_at_dev,
                                 ramp_on, DT_NS, backend=backend)
    restored = trace_res[:, :, n - 1] >= 0.95 * vdd
    t_res_dur = _first_crossing_ns(restored, DT_NS)

    # ---------------- PRE: WL down, equalize ----------------------------
    n_pre = N_PRE_STEPS
    t_grid_pre = (jnp.arange(n_pre) + 1) * DT_NS
    ramp_down = wl_ramp(tech, t_grid_pre, rising=False).astype(jnp.float32)
    t_res_idx = jnp.where(jnp.isnan(t_res_dur), T_RESTORE_NS, t_res_dur)
    idx_res = jnp.clip((t_res_idx / DT_NS).astype(jnp.int32) - 1, 0,
                       n_res - 1)
    v_end_res = trace_res[idx_res, jnp.arange(b), :]
    g_clamp_pre = zero_clamp.at[:, :n - 1].set(1.0 / tech.r_pre_kohm)
    v_clamp_pre = jnp.full((b, n), vpre, jnp.float32)
    trace_pre = ops.rc_multistep(c, g, g_clamp_pre, v_clamp_pre, v_end_res,
                                 ramp_down, DT_NS, backend=backend)
    equalized = jnp.max(jnp.abs(trace_pre[:, :, :n - 1] - vpre), axis=-1) <= 5e-3
    t_pre = _first_crossing_ns(equalized, DT_NS)

    t_sense, t_restore, trc = _regen_and_totals(
        tech.sa_tau_ns, tech.t_overhead_ns, t_dev, dv_sense, t_res_dur, t_pre)
    traces = {"act": trace_act, "restore": trace_res, "pre": trace_pre}
    if replica:
        traces["replica"] = trace_rep
    return RowCycleResult(
        t_sense_ns=t_sense, t_restore_ns=t_restore, t_precharge_ns=t_pre,
        trc_ns=trc, dv_sense_v=dv_sense, traces=traces, t_fire_ns=t_dev)


def nominal_trc_ns(tech: TechCal, scheme: str = "sel_strap",
                   layers: int | None = None) -> jnp.ndarray:
    """Nominal tRC at the technology's target layer count."""
    if layers is None:
        layers = tech.layers_target
    return simulate_row_cycle(tech, scheme, jnp.asarray([layers])).trc_ns[0]
