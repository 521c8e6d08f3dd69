"""Design-space exploration — the "co-optimization" of the paper's title.

Array-native flow (the public API):

    space = DesignSpace.paper_grid()        # declarative (core.space)
    batch = sweep(space)                    # ONE vectorized evaluation
    front = pareto_front(batch)             # masked array dominance
    best  = best_design(batch)              # paper's selection rule

`sweep` lowers the whole (tech x scheme x layers [x corners]) space to a
flat operand batch and pipes every metric — density, margin, energy,
bonding geometry, and the fused row-cycle tRC — through array ops end to
end: no per-combo Python loop anywhere, and the resulting `DesignBatch`
is a jit/vmap/sharding-compatible pytree (see core.batch).

This is what turns the calibrated physics models into the paper's
conclusion: the selector+strap topology is the only corner that is
simultaneously manufacturable (pitch), functional (margin), and
fast/efficient.

Legacy surface: `full_sweep` / `evaluate_grid` still return the old
`list[DesignPoint]` (deprecated; thin views over the batch), and
`pareto_front` / `best_design` accept either a `DesignBatch` or a list.
"""

from __future__ import annotations

import warnings
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from .. import obs
from . import calibration as cal
from . import contracts
from .batch import DesignBatch, DesignPoint
from .calibration import TECHS, TechCal
from .density import (bit_density_gb_mm2, bit_density_lowered,
                      stack_height_lowered, stack_height_um)
from .energy import (read_energy_fj, read_energy_lowered, write_energy_fj,
                     write_energy_lowered)
from .netlist import build_ladder_lowered, effective_cbl_ff
from .parasitics import bl_parasitics_lowered
from .routing import SCHEMES, bonding_geometry, bonding_geometry_lowered
from .sense import sense_margin_lowered, sense_margin_mv
from .space import MC_AXES, MC_LOG_W, DesignSpace, SpaceView
from . import transient
from .transient import simulate_row_cycle, simulate_row_cycle_many

__all__ = [
    "DesignBatch", "DesignPoint", "DesignSpace",
    "SweepPlan", "plan_sweep", "finalize_sweep",
    "score_columns", "score_from_events", "assemble_batch",
    "sweep", "pareto_mask", "pareto_front", "best_design", "as_batch",
    "full_sweep", "evaluate_grid", "sweep_combos",
]

# Corner axes `sweep` knows how to route into the physics models (the
# reserved mc_* channels of a with_mc space ride the same mechanism).
SUPPORTED_CORNER_AXES = ("rh_toggles", "trc_cycles")


# ---------------------------------------------------------------------------
# The vectorized sweep
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SweepPlan:
    """A lowered, dispatch-ready sweep: everything `sweep` does before the
    fused engine runs.

    The plan/finalize split is the serving seam: `plan_sweep` lowers a
    space to its operand batch, `finalize_sweep` turns a transient result
    back into the scored `DesignBatch` — and BOTH halves are the exact
    code `sweep` itself runs, so a caller that dispatches the operands
    elsewhere (e.g. `serving.dse_service` packing many clients' plans
    into one shared slab) gets results bit-identical to a direct
    `dse.sweep` by construction.
    """
    space: DesignSpace
    sp: object                         # LoweredSpace
    par: object                        # BLParasitics over the lowered space
    operands: transient.FusedOperands | None   # None when transient is off

    def __len__(self) -> int:
        return len(self.sp)

    @property
    def with_transient(self) -> bool:
        return self.operands is not None


def plan_sweep(space: DesignSpace | None = None,
               with_transient: bool = True) -> SweepPlan:
    """Lower a `DesignSpace` to a dispatch-ready `SweepPlan`.

    Validates corner axes, assembles the parasitic decomposition, and
    (when the transient is on) lowers the whole space to ONE
    `FusedOperands` batch — the heavy per-request work a warm serving
    engine wants to do once per space, off the dispatch path.
    """
    if space is None:
        space = DesignSpace.paper_grid()
    with obs.span("dse.plan"):
        with obs.span("dse.plan.lower"):
            sp = space.lower()
        unknown = [k for k in sp.corners
                   if k not in SUPPORTED_CORNER_AXES and k not in MC_AXES
                   and k != MC_LOG_W]
        if unknown:
            raise ValueError(f"unsupported corner axes {unknown}; sweep "
                             f"understands {SUPPORTED_CORNER_AXES}")
        with obs.span("dse.plan.parasitics"):
            par = bl_parasitics_lowered(sp)
        operands = None
        if with_transient:
            with obs.span("dse.plan.operands"):
                ladder_c, ladder_g = build_ladder_lowered(sp, par)
                operands = transient.lower_design_operands(
                    sp, ladder_c=ladder_c, ladder_g=ladder_g)
    return SweepPlan(space=space, sp=sp, par=par, operands=operands)


def score_columns(view, cbl_ff, trc=None, t_sense=None, t_fire=None,
                  dv_sense=None) -> dict:
    """Pure-jnp per-row scoring of a design-space view -> column dict.

    `view` is a `SpaceView` (or any traceable LoweredSpace-protocol
    object); `cbl_ff` the per-point total BL capacitance from the plan's
    parasitic decomposition.  The transient columns (`trc`, `t_sense`,
    `t_fire`, `dv_sense`) are either all given (post-rollup, design-point
    length) or all None (`with_transient=False`: NaN-filled).

    Every output is an elementwise (B,) array — no cross-row ops — so
    the function is batch-size independent and runs identically whether
    jitted whole-batch (the sequential sweep) or inside a per-device
    `shard_map` body (the sharded sweep).  Keys match `DesignBatch`
    field names; `assemble_batch` zips them with the host-side identity
    columns.
    """
    cbl = jnp.asarray(cbl_ff, jnp.float32)
    dens = bit_density_lowered(view)
    height = stack_height_lowered(view)
    margin = sense_margin_lowered(view, cbl_ff=cbl)
    margin_d = sense_margin_lowered(view, with_disturb=True, cbl_ff=cbl)
    e_wr = write_energy_lowered(view, cbl_ff=cbl)
    e_rd = read_energy_lowered(view, cbl_ff=cbl)
    geom = bonding_geometry_lowered(view)

    if trc is not None:
        # margin actually available at the SA fire: the simulated
        # developed signal at the enable instant minus the SA offset
        # (per-sample on MC spaces, calibrated corner otherwise) — the
        # closed-timing counterpart of the analytic charge-share margin.
        sa_offset = view.corner("mc_sa_offset_mv", None)
        if sa_offset is None:
            sa_offset = jnp.asarray(view.tech("sa_offset_mv"), jnp.float32)
        margin_fire = (dv_sense * 1e3 - sa_offset).astype(jnp.float32)
    else:
        trc = jnp.full((len(view),), jnp.nan, jnp.float32)
        t_sense = trc
        t_fire = trc
        margin_fire = trc

    valid = jnp.asarray(view.valid)
    feasible = (geom.manufacturable
                & (margin >= cal.MIN_FUNCTIONAL_MARGIN_MV - 1e-9)
                & (margin_d >= cal.MIN_DISTURBED_MARGIN_MV - 1e-9)
                & valid)
    if dv_sense is not None:
        # a design whose timing never closed (NaN tRC: a phase timed out,
        # or the WL ramp starved signal development past the ACT window)
        # is invalid as a design, not merely slow
        feasible = feasible & jnp.isfinite(trc)

    return dict(
        density_gb_mm2=dens, height_um=height, cbl_ff=cbl,
        margin_mv=margin, margin_disturbed_mv=margin_d,
        trc_ns=jnp.asarray(trc, jnp.float32),
        t_sense_ns=jnp.asarray(t_sense, jnp.float32),
        t_fire_ns=jnp.asarray(t_fire, jnp.float32),
        margin_fire_mv=margin_fire, e_write_fj=e_wr, e_read_fj=e_rd,
        hcb_pitch_um=geom.hcb_pitch_um.astype(jnp.float32),
        blsa_area_um2=geom.blsa_area_um2.astype(jnp.float32),
        manufacturable=geom.manufacturable, feasible=feasible)


def score_from_events(view, cbl_ff, sa_tau_ns, t_overhead_ns, evt) -> dict:
    """Rollup + scoring from raw fused-engine event columns -> column dict.

    `evt` is the engine's (B_ops, 4) output BEFORE replica de-interleave;
    `sa_tau_ns` / `t_overhead_ns` are the matching operand-length rollup
    vectors.  On replica spaces (`view.replica`, static) the main rows
    sit at odd indices and B_ops == 2 * len(view).

    This is THE scoring program of the sweep: the sequential path runs
    it under one `jax.jit`, the sharded path runs the same function as a
    per-device `shard_map` body (`launch.shard`) — identical per-row
    arithmetic, hence bit-identical columns.
    """
    sa_tau = jnp.asarray(sa_tau_ns, jnp.float32)
    overhead = jnp.asarray(t_overhead_ns, jnp.float32)
    if view.replica:
        evt = evt[1::2]
        sa_tau = sa_tau[1::2]
        overhead = overhead[1::2]
    t_sense, _t_restore, trc = transient._regen_and_totals(
        sa_tau, overhead, evt[:, 0], evt[:, 1], evt[:, 2], evt[:, 3])
    return score_columns(view, cbl_ff, trc=trc, t_sense=t_sense,
                         t_fire=evt[:, 0], dv_sense=evt[:, 1])


# The ONE compiled scoring program (see score_from_events): module-level
# so the sequential sweep, the serving finalize, and repeat calls all hit
# the same jit cache.
_score_columns_jit = jax.jit(score_columns)
_score_from_events_jit = jax.jit(score_from_events)


def assemble_batch(sp, cols: dict) -> DesignBatch:
    """Zip scored metric columns with a lowered space's identity columns
    into the contract-checked `DesignBatch`.

    `cols` is a `score_columns`-shaped dict (device or host arrays —
    the sharded sweep hands back gathered numpy columns); `sp` supplies
    the per-point identity (indices, layers, validity, corner values)
    and the static names/layout.
    """
    batch = DesignBatch(
        tech_idx=jnp.asarray(sp.tech_idx), scheme_idx=jnp.asarray(sp.scheme_idx),
        layers=sp.layers, valid=jnp.asarray(sp.valid),
        corners={k: jnp.asarray(v) for k, v in sp.corners.items()},
        tech_names=sp.tech_names, scheme_names=sp.scheme_names,
        n_samples=sp.samples, base_len=sp.base_len,
        **{k: jnp.asarray(v) for k, v in cols.items()})
    contracts.check_batch(batch, where="dse.sweep")
    return batch


def finalize_sweep(plan: SweepPlan,
                   res: transient.RowCycleResult | None = None) -> DesignBatch:
    """Score a planned sweep into a `DesignBatch`.

    `res` is the fused-engine result for `plan.operands` (None iff the
    plan was made with `with_transient=False`).  This is the second half
    of `sweep`: the jitted `score_from_events` program rolls the raw
    engine events up and scores every metric as flat (B,) arrays over
    the plan's lowered space — the same program the sharded driver runs
    per device — then `assemble_batch` zips in the identity columns.
    """
    if plan.with_transient != (res is not None):
        raise ValueError(
            "finalize_sweep needs the fused-engine result exactly when "
            "the plan lowered transient operands (with_transient="
            f"{plan.with_transient}, res={'set' if res is not None else 'None'})")
    with obs.span("dse.finalize"):
        view = SpaceView.from_lowered(plan.sp)
        cbl = jnp.asarray(plan.par.c_bl_total_ff, jnp.float32)
        if res is None:
            cols = _score_columns_jit(view, cbl)
        elif res.events is not None:
            cols = _score_from_events_jit(
                view, cbl, plan.operands.sa_tau_ns,
                plan.operands.t_overhead_ns, res.events)
        else:
            # result built without raw events (legacy construction): score
            # from the rolled-up columns; matches the events path up to the
            # compiler's instruction scheduling of the rollup.
            cols = _score_columns_jit(view, cbl, res.trc_ns, res.t_sense_ns,
                                      res.t_fire_ns, res.dv_sense_v)
        return assemble_batch(plan.sp, cols)


def sweep(space: DesignSpace | None = None, with_transient: bool = True,
          backend: str = "auto",
          b_chunk: int = transient.DEFAULT_B_CHUNK,
          sharding=None) -> DesignBatch:
    """Score a whole `DesignSpace` in one vectorized pass -> `DesignBatch`.

    All metrics are computed as flat (B,) arrays over the lowered space;
    the transient row-cycle times come from ONE chunked pass through the
    fused engine (`transient.simulate_row_cycle_many` on the lowered
    operand batch) — never a per-combo transient call.  Internally this
    is `plan_sweep` -> fused dispatch -> `finalize_sweep`; the split is
    public so a warm serving engine (`serving.dse_service`) can pack many
    plans into one shared dispatch and finalize each identically.

    `sharding` (a `jax.sharding.Mesh` or `NamedSharding`) distributes
    BOTH the fused dispatch and the metric scoring over a device mesh —
    each device (and each host under multi-process JAX) evaluates and
    scores its own slab of the grid via `repro.launch.shard`, so no
    per-point intermediate ever materializes host-side; results are
    bit-identical to the single-host path (which remains the
    equivalence oracle).
    """
    if sharding is not None and not with_transient:
        raise ValueError(
            "sharding= only distributes the fused transient dispatch; a "
            "with_transient=False sweep is host-side array ops with "
            "nothing to shard — pass sharding=None")
    with obs.span("dse.sweep"):
        plan = plan_sweep(space, with_transient=with_transient)
        if plan.operands is not None and sharding is not None:
            from ..launch import shard
            cols = shard.sharded_sweep_columns(plan, sharding, backend=backend,
                                               b_chunk=b_chunk)
            return assemble_batch(plan.sp, cols)
        res = None
        if plan.operands is not None:
            res = simulate_row_cycle_many(plan.operands, backend=backend,
                                          b_chunk=b_chunk)
        return finalize_sweep(plan, res)


# ---------------------------------------------------------------------------
# Pareto front / selection (vectorized dominance)
# ---------------------------------------------------------------------------

def pareto_mask(batch: DesignBatch, require_feasible: bool = True,
                block: int = 4096, extra_maximize=(),
                extra_minimize=(), sharding=None) -> jnp.ndarray:
    """Non-dominated mask maximizing density & disturbed margin, minimizing
    tRC & read energy.  Pure jnp (jit-compatible): the O(n^2) pairwise
    comparison runs as masked broadcasts over fixed-size dominator blocks,
    so peak memory is O(block * B), not O(B^2) — million-point sharded
    sweeps stay tractable (tune `block` down for very large batches).

    `extra_maximize` / `extra_minimize` append further (B,) objective
    columns — e.g. a Monte-Carlo yield column
    (`batch.mc_summary(...).corners["yield_frac"]`) as a maximized
    objective alongside the nominal metrics.

    `sharding` (Mesh / NamedSharding) distributes the dominator blocks
    over a device mesh instead of the host loop: each device tests its
    own dominator slab against the (replicated) full batch and the
    per-device dominated masks OR-reduce across the mesh
    (`launch.shard.sharded_pareto_mask`).  Dominance tests are exact
    comparisons and boolean OR is order-independent, so the sharded mask
    is bit-identical to the sequential one.

    NaN metrics (e.g. tRC with `with_transient=False`) never dominate and
    are never dominated — matching the legacy pairwise semantics.
    """
    cand = batch.valid
    if require_feasible:
        cand = cand & batch.feasible
    hi = jnp.stack([batch.density_gb_mm2, batch.margin_disturbed_mv,
                    *(jnp.asarray(x) for x in extra_maximize)], axis=1)
    lo = jnp.stack([batch.trc_ns, batch.e_read_fj,
                    *(jnp.asarray(x) for x in extra_minimize)], axis=1)
    if sharding is not None:
        from ..launch import shard
        dominated = shard.sharded_pareto_dominated(hi, lo, cand, sharding,
                                                   block=block)
        return cand & ~jnp.asarray(dominated)
    b = hi.shape[0]
    dominated = jnp.zeros((b,), bool)
    for i0 in range(0, b, block):          # dominator blocks (static count)
        hi_i, lo_i = hi[i0:i0 + block], lo[i0:i0 + block]
        cand_i = cand[i0:i0 + block]
        ge = ((hi_i[:, None, :] >= hi[None, :, :]).all(-1)
              & (lo_i[:, None, :] <= lo[None, :, :]).all(-1))
        gt = ((hi_i[:, None, :] > hi[None, :, :]).any(-1)
              | (lo_i[:, None, :] < lo[None, :, :]).any(-1))
        dominated |= (ge & gt & cand_i[:, None] & cand[None, :]).any(axis=0)
    return cand & ~dominated


def as_batch(points_or_batch) -> DesignBatch:
    """Normalize any selection input to a `DesignBatch`.

    THE compatibility adapter of the selection layer: a `DesignBatch`
    passes through untouched; a legacy `list[DesignPoint]` (or any
    iterable of point-shaped objects) is bridged via
    `DesignBatch.from_points`.  `pareto_front` / `best_design` are
    batch-native internally and use this adapter at their boundary —
    list-in/list-out back-compat lives here and nowhere else.
    """
    if isinstance(points_or_batch, DesignBatch):
        return points_or_batch
    return DesignBatch.from_points(list(points_or_batch))


def _legacy_points(points_or_batch):
    """The list half of the back-compat boundary: the materialized legacy
    list when the caller passed one (so outputs keep list form), else
    None for the batch-native path."""
    if isinstance(points_or_batch, DesignBatch):
        return None
    return list(points_or_batch)


def pareto_front(points_or_batch, require_feasible: bool = True,
                 extra_maximize=(), extra_minimize=(), sharding=None):
    """Non-dominated set.  `DesignBatch` in -> filtered `DesignBatch` out;
    legacy `list[DesignPoint]` in -> list out (order preserved), bridged
    through the `as_batch` adapter.  Extra (B,) objective columns (e.g.
    an MC yield column) and `sharding` (distribute the dominance test
    over a device mesh) pass through to `pareto_mask`."""
    points = _legacy_points(points_or_batch)
    batch = as_batch(points_or_batch if points is None else points)
    mask = np.asarray(pareto_mask(batch, require_feasible,
                                  extra_maximize=extra_maximize,
                                  extra_minimize=extra_minimize,
                                  sharding=sharding))
    if points is None:
        return batch.select(mask)
    return [p for p, m in zip(points, mask) if m]


def best_design(points_or_batch,
                density_target: float = cal.DENSITY_TARGET_GB_MM2,
                min_yield: float | None = None, yield_frac=None):
    """The paper's selection rule: hit the density target with a functional,
    manufacturable design; break ties by tRC then read energy then height.
    Accepts a `DesignBatch` or the legacy list; returns a `DesignPoint`
    (or None if nothing qualifies).

    `min_yield` adds a Monte-Carlo yield floor: candidates must have
    `yield_frac >= min_yield`, where `yield_frac` is an explicit (B,)
    column or defaults to the batch's `corners["yield_frac"]` (set by
    `DesignBatch.mc_summary`).
    """
    points = _legacy_points(points_or_batch)
    batch = as_batch(points_or_batch if points is None else points)
    cand = (np.asarray(batch.valid) & np.asarray(batch.feasible)
            & (np.asarray(batch.density_gb_mm2) >= density_target - 1e-9))
    if min_yield is not None:
        if yield_frac is None:
            yield_frac = batch.corners.get("yield_frac")
        if yield_frac is None:
            raise ValueError(
                "min_yield needs a yield column: pass yield_frac= or use "
                "a batch with corners['yield_frac'] (DesignBatch.mc_summary)")
        cand &= np.asarray(yield_frac) >= min_yield - 1e-9
    idx = np.flatnonzero(cand)
    if idx.size == 0:
        return None
    trc = np.asarray(batch.trc_ns, np.float64)[idx]
    trc = np.where(np.isnan(trc), np.inf, trc)
    e_rd = np.asarray(batch.e_read_fj, np.float64)[idx]
    height = np.asarray(batch.height_um, np.float64)[idx]
    order = np.lexsort((height, e_rd, trc))     # last key is primary
    best = int(idx[order[0]])
    return points[best] if points is not None else batch.point(best)


# ---------------------------------------------------------------------------
# Legacy list[DesignPoint] surface (deprecated)
# ---------------------------------------------------------------------------

def evaluate_grid(tech: TechCal, scheme: str, layers: np.ndarray,
                  with_transient: bool = True,
                  trc: np.ndarray | None = None) -> list[DesignPoint]:
    """Evaluate a vector of layer counts for one (tech, scheme).

    Deprecated reference path: per-(tech, scheme) scalar evaluation kept
    as the equivalence oracle for the vectorized `sweep`.  `trc` may carry
    precomputed row-cycle times; otherwise the transient engine runs here.
    """
    arr = jnp.asarray(layers)
    dens = np.asarray(bit_density_gb_mm2(tech, arr))
    height = np.asarray(stack_height_um(tech, arr))
    cbl = np.asarray(effective_cbl_ff(tech, scheme, arr))
    margin = np.asarray(sense_margin_mv(tech, scheme, arr))
    margin_d = np.asarray(sense_margin_mv(tech, scheme, arr, with_disturb=True))
    e_wr = np.asarray(write_energy_fj(tech, scheme, arr))
    e_rd = np.asarray(read_energy_fj(tech, scheme, arr))
    geom = bonding_geometry(tech, scheme)
    pitch = float(geom.hcb_pitch_um)
    blsa = float(geom.blsa_area_um2)
    manufacturable = bool(geom.manufacturable) or tech.baseline_2d
    if trc is not None:
        trc = np.asarray(trc)
    elif with_transient:
        trc = np.asarray(simulate_row_cycle(tech, scheme, arr).trc_ns)
    else:
        trc = np.full(len(layers), np.nan)

    pts = []
    for i, layer in enumerate(np.asarray(layers)):  # repro-lint: disable=RL002  (scalar equivalence oracle for tests, not the fused sweep path)
        feas = (manufacturable
                and margin[i] >= cal.MIN_FUNCTIONAL_MARGIN_MV - 1e-9
                and margin_d[i] >= cal.MIN_DISTURBED_MARGIN_MV - 1e-9)
        pts.append(DesignPoint(
            tech=tech.name, scheme=scheme, layers=int(layer),
            density_gb_mm2=float(dens[i]), height_um=float(height[i]),
            cbl_ff=float(cbl[i]), margin_mv=float(margin[i]),
            margin_disturbed_mv=float(margin_d[i]), trc_ns=float(trc[i]),
            e_write_fj=float(e_wr[i]), e_read_fj=float(e_rd[i]),
            hcb_pitch_um=pitch, blsa_area_um2=blsa, feasible=bool(feas)))
    return pts


def sweep_combos(layer_grid: np.ndarray) -> list[tuple[TechCal, str, np.ndarray]]:
    """The (tech, scheme, layer-grid) combos of the full design space.

    Deprecated: capability flags on each registered `TechCal` drive this
    now (no name-based special cases); new code should build a
    `DesignSpace` instead.  Removal timeline: docs/api.md.
    """
    warnings.warn(
        "dse.sweep_combos is deprecated and will be removed (see "
        "docs/api.md for the timeline); build a DesignSpace "
        "(DesignSpace.paper_grid / product) instead",
        DeprecationWarning, stacklevel=2)
    combos: list[tuple[TechCal, str, np.ndarray]] = []
    for tech in TECHS.values():
        schemes = tech.allowed_schemes or tuple(SCHEMES)
        grid = (np.asarray(tech.layer_grid) if tech.layer_grid is not None
                else layer_grid)
        for scheme in schemes:
            combos.append((tech, scheme, grid))
    return combos


def full_sweep(layer_grid: np.ndarray | None = None,
               with_transient: bool = True) -> list[DesignPoint]:
    """Sweep the whole (tech x scheme x layers) design space.

    Deprecated compatibility shim: equivalent to
    `sweep(DesignSpace.paper_grid(layer_grid)).to_points()`.  One batched
    fused-engine pass computes every transient, exactly like `sweep`.
    Removal timeline: docs/api.md.
    """
    warnings.warn(
        "dse.full_sweep is deprecated and will be removed (see docs/api.md "
        "for the timeline); use dse.sweep(DesignSpace.paper_grid(...)) and "
        "consume the DesignBatch columns",
        DeprecationWarning, stacklevel=2)
    grid = None if layer_grid is None else tuple(
        float(x) for x in np.asarray(layer_grid).reshape(-1))
    space = DesignSpace.paper_grid(layer_grid=grid)
    with warnings.catch_warnings():
        # the shim IS the deprecated surface; its internal to_points call
        # must not double-warn the caller
        warnings.simplefilter("ignore", DeprecationWarning)
        return sweep(space, with_transient=with_transient).to_points()
