"""End-to-end design-space exploration — the paper's co-optimization flow.

Array-native API: declare a `DesignSpace`, score it in ONE vectorized
`dse.sweep` (density, margins, energy, bonding geometry, and the fused
row-cycle tRC all as flat batch arrays), then extract the Pareto front and
the selected design with masked array ops — i.e., regenerates the
substance of Table I / Fig. 9(c) without a single per-combo Python loop.

Run:  PYTHONPATH=src python examples/dram_codesign.py [--smoke] [--mc [N]]
                                                      [--sharded] [--replica]

`--smoke` sweeps a reduced layer grid on CPU — the fast API-regression
mode `tools/ci_check.sh` runs pre-merge.  `--mc [N]` additionally fans
the same space out to N Monte-Carlo samples per design point (SA-offset
+ Vth variation, still ONE fused transient batch) and reports margin/tRC
*yield* instead of nominal-only numbers.  `--sharded` distributes the
fused dispatch over every visible jax device (one slab per device; run
under XLA_FLAGS=--xla_force_host_platform_device_count=8 to try it on a
laptop) — results are bit-identical to the single-host sweep.
`--replica` closes the SA-enable timing with a replica bitline per design
point (instead of the fixed own-90% sense window) and prints a
fixed-vs-closed comparison on the Table-1 anchor points.
"""

import argparse

import numpy as np

from repro.core import calibration as cal
from repro.core import dse
from repro.core.space import DesignSpace
from repro.runtime.compile_cache import enable_compile_cache

parser = argparse.ArgumentParser()
parser.add_argument("--smoke", action="store_true",
                    help="reduced layer grid (fast CI smoke mode)")
parser.add_argument("--mc", type=int, nargs="?", const=128, default=0,
                    metavar="SAMPLES",
                    help="Monte-Carlo samples per design point (default "
                         "128 when the flag is given without a value)")
parser.add_argument("--mc-key", type=int, default=0,
                    help="PRNG seed for the Monte-Carlo draws")
parser.add_argument("--mc-tail", type=int, nargs="?", const=4096, default=0,
                    metavar="SAMPLES",
                    help="importance-sampled deep-tail (ppm) margin-yield "
                         "estimate under correlated within-die variation "
                         "(default 4096 samples when the flag is given "
                         "without a value)")
parser.add_argument("--mc-tail-shift", type=float, default=4.0,
                    help="proposal shift (sigmas) of the SA-offset tail "
                         "draws")
parser.add_argument("--sharded", action="store_true",
                    help="shard the fused sweep over all jax devices")
parser.add_argument("--replica", action="store_true",
                    help="replica-bitline timing closure: the SA enable "
                         "fires on a per-point replica column's crossing "
                         "instead of the fixed own-90%% window")
args = parser.parse_args()
enable_compile_cache()

sharding = None
if args.sharded:
    import jax
    from repro.launch.shard import sweep_sharding
    sharding = sweep_sharding()          # all devices, one "batch" axis
    print(f"sharding the sweep over {jax.device_count()} device(s)")

grid = (64, 87, 137) if args.smoke else None
space = DesignSpace.paper_grid(layer_grid=grid)
if args.replica:
    space = space.with_replica()
    print("replica-closed SA-enable timing (per-point replica bitline)")
print(f"sweeping design space ({len(space)} design points, one fused "
      "transient batch)...")
batch = dse.sweep(space, sharding=sharding)

n_feas = int(np.asarray(batch.feasible).sum())
print(f"\n{len(batch)} design points, {n_feas} feasible "
      f"(margin nominal>={cal.MIN_FUNCTIONAL_MARGIN_MV:.0f} mV, "
      f"disturbed>={cal.MIN_DISTURBED_MARGIN_MV:.0f} mV, "
      f"pitch>={cal.HCB_MIN_MANUFACTURABLE_PITCH_UM} um)")

front = dse.pareto_front(batch)          # DesignBatch -> DesignBatch
print(f"\nPareto front ({len(front)} points):")
print(f"{'tech':5s} {'scheme':10s} {'L':>4s} {'Gb/mm2':>7s} {'dV(mV)':>7s} "
      f"{'dV+dist':>8s} {'tRC(ns)':>8s} {'Erd(fJ)':>8s} {'pitch':>6s}")
order = np.argsort(-np.asarray(front.density_gb_mm2))[:12]
for i in order:
    print(f"{front.tech_col[i]:5s} {front.scheme_col[i]:10s} "
          f"{int(front.layers[i]):4d} "
          f"{float(front.density_gb_mm2[i]):7.2f} "
          f"{float(front.margin_mv[i]):7.0f} "
          f"{float(front.margin_disturbed_mv[i]):8.0f} "
          f"{float(front.trc_ns[i]):8.2f} "
          f"{float(front.e_read_fj[i]):8.2f} "
          f"{float(front.hcb_pitch_um[i]):6.2f}")

best = dse.best_design(batch)            # paper's selection rule
print(f"\nselected design (paper's rule: hit {cal.DENSITY_TARGET_GB_MM2} "
      f"Gb/mm2, min tRC):")
print(f"  {best.tech} / {best.scheme} @ {best.layers} layers -> "
      f"{best.density_gb_mm2:.2f} Gb/mm2, tRC {best.trc_ns:.2f} ns, "
      f"margin {best.margin_mv:.0f} mV ({best.margin_disturbed_mv:.0f} mV "
      f"w/ FBE+RH), E_rd {best.e_read_fj:.2f} fJ, "
      f"HCB pitch {best.hcb_pitch_um:.2f} um")

# Table-1 anchors, read straight off the batch columns
tech_col, scheme_col = batch.tech_col, batch.scheme_col
def row(tech, scheme, layers):
    (i,) = [i for i in range(len(batch))
            if tech_col[i] == tech and scheme_col[i] == scheme
            and int(batch.layers[i]) == layers]
    return i

print("\nTable I anchors (from the DesignBatch):")
for tech, scheme, L in (("si", "sel_strap", 137), ("aos", "sel_strap", 87),
                        ("d1b", "direct", 1)):
    i = row(tech, scheme, L)
    print(f"  {tech:4s} {scheme:10s} @{L:3d}L: "
          f"{float(batch.density_gb_mm2[i]):4.2f} Gb/mm2  "
          f"tRC {float(batch.trc_ns[i]):5.2f} ns  "
          f"E_wr {float(batch.e_write_fj[i]):5.2f} fJ  "
          f"E_rd {float(batch.e_read_fj[i]):4.2f} fJ")

# ---------------------------------------------------------------------------
# Replica timing closure (--replica): fixed t_sense vs replica-closed on
# the Table-1 anchors — what per-point timing closure buys (and costs).
# ---------------------------------------------------------------------------
if args.replica:
    from repro.core.report import replica_timing_table
    cmp = replica_timing_table()
    print("\nfixed t_sense vs replica-closed (Table-1 anchors):")
    print(f"  {'tech':4s} {'cells':>5s} {'tRC fix':>8s} {'tRC clo':>8s} "
          f"{'dtRC':>6s} {'fire fix':>8s} {'fire clo':>8s} {'mrg@fire':>9s}")
    for tech, r in cmp.items():
        print(f"  {tech:4s} {r['replica_cells']:5.1f} "
              f"{r['trc_fixed_ns']:8.2f} {r['trc_closed_ns']:8.2f} "
              f"{r['trc_delta_ns']:6.2f} {r['t_fire_fixed_ns']:8.2f} "
              f"{r['t_fire_closed_ns']:8.2f} "
              f"{r['margin_fire_closed_mv']:9.1f}")

i_d1b = row("d1b", "direct", 1)
d1b_trc = float(batch.trc_ns[i_d1b])
d1b_erd = float(batch.e_read_fj[i_d1b])
d1b_dens = float(batch.density_gb_mm2[i_d1b])
print(f"\nvs D1b baseline: density x{best.density_gb_mm2 / d1b_dens:.1f}, "
      f"tRC x{d1b_trc / best.trc_ns:.2f} faster, "
      f"E_rd x{d1b_erd / best.e_read_fj:.2f} lower")

# ---------------------------------------------------------------------------
# Monte-Carlo yield (--mc): same space, fanned out to N samples per point,
# still ONE chunked fused row-cycle dispatch.
# ---------------------------------------------------------------------------
if args.mc:
    print(f"\n== Monte-Carlo yield: {args.mc} samples/design "
          f"(key {args.mc_key}, {len(space) * args.mc} rows, one fused "
          "batch) ==")
    mc_batch = dse.sweep(space.with_mc(samples=args.mc, key=args.mc_key),
                         sharding=sharding)
    trc_ceiling = 1.1 * d1b_trc / 2.0        # spec: comfortably beat D1b/2
    summary = mc_batch.mc_summary(margin_mv=cal.MIN_FUNCTIONAL_MARGIN_MV,
                                  trc_ns=trc_ceiling)
    yf = np.asarray(summary.corners["yield_frac"])
    p05_margin = np.asarray(mc_batch.quantile(0.05, "margin_mv"))
    p95_trc = np.asarray(mc_batch.quantile(0.95, "trc_ns"))

    print(f"spec: margin>={cal.MIN_FUNCTIONAL_MARGIN_MV:.0f} mV & "
          f"tRC<={trc_ceiling:.1f} ns")
    print("Table I anchors (yield over samples, p05 margin, p95 tRC):")
    for tech, scheme, L in (("si", "sel_strap", 137),
                            ("aos", "sel_strap", 87), ("d1b", "direct", 1)):
        i = row(tech, scheme, L)             # summary keeps the base layout
        print(f"  {tech:4s} {scheme:10s} @{L:3d}L: "
              f"yield {yf[i]:5.1%}  "
              f"margin_p05 {p05_margin[i]:6.1f} mV  "
              f"tRC_p95 {p95_trc[i]:5.2f} ns")

    best_y = dse.best_design(summary, min_yield=0.9)
    if best_y is None:
        print("no design meets the density target at >=90% yield")
    else:
        print(f"highest-yield selection (>=90% yield, paper's rule): "
              f"{best_y.tech} / {best_y.scheme} @ {best_y.layers} layers -> "
              f"yield {yf[row(best_y.tech, best_y.scheme, best_y.layers)]:.1%}, "
              f"median tRC {best_y.trc_ns:.2f} ns")

# ---------------------------------------------------------------------------
# Deep-tail ppm yield (--mc-tail): importance-sampled margin-tail estimate
# of the Table-1 target points under correlated within-die variation.  The
# SA-offset proposal is shifted into the failure tail; exact per-row
# log-weights ride the batch as the reserved mc_log_w channel and
# yield_ppm turns the weighted failures into a ppm estimate + CI + a
# tail-ESS diagnostic (NaN when too few effective failures were seen).
# ---------------------------------------------------------------------------
if args.mc_tail:
    shift = args.mc_tail_shift
    print(f"\n== ppm-tail yield: {args.mc_tail} importance samples/design "
          f"(SA proposal shifted {shift:.1f} sigma, correlated "
          "within-die draws) ==")
    tail_space = DesignSpace.paper_targets().with_mc(
        samples=args.mc_tail, key=args.mc_key, corr=1.0,
        tail_shift=(shift, 0.0), tail_scale=(1.2, 1.0))
    tail_batch = dse.sweep(tail_space, with_transient=False)
    floor = cal.MIN_FUNCTIONAL_MARGIN_MV
    ppm = tail_batch.yield_ppm(margin_mv=floor)
    base = tail_batch.base_len
    print(f"spec: margin>={floor:.0f} mV; failure rate in ppm "
          "(95% CI, tail ESS):")
    for i, tech in enumerate(tail_batch.tech_col[:base]):
        est = float(np.asarray(ppm["fail_ppm"])[i])
        lo = float(np.asarray(ppm["fail_ppm_lo"])[i])
        hi = float(np.asarray(ppm["fail_ppm_hi"])[i])
        ess = float(np.asarray(ppm["ess"])[i])
        layers = int(np.asarray(tail_batch.layers)[i])
        if np.isnan(est):
            print(f"  {tech:4s} @{layers:3d}L: no estimate "
                  f"(tail ESS {ess:.1f} too low — raise --mc-tail or "
                  "retune --mc-tail-shift)")
        else:
            print(f"  {tech:4s} @{layers:3d}L: {est:10.3f} ppm "
                  f"[{lo:.3f}, {hi:.3f}]  ESS {ess:.0f}")
