"""Plain reference of one STCO study: lowering, physics, row cycle, scoring.

Written in NumPy from the model's equations, independent of the program
under test: it imports nothing of `repro`, and every calibration value
comes from the configuration file (`configs/<config>.json`).  `dtype` is
the arithmetic precision: float64 for the reference, bfloat16 (through
`ml_dtypes`) for the control that must fail the comparison.

A study is described by a spec (see `spaces.py`): design entries
`[[tech, scheme, [layers...]], ...]`, corner axes `[[name, [values...]], ...]`
(outermost: combo-major, each combo a block of the base rows), optional
Monte-Carlo sampling `{"samples", "key", "corr"}` (outermost of all:
sample-major) and the replica flag.  `study_columns(config, spec, rows)`
returns the scored columns of the requested flat rows only, so a sample
of a large study costs what the sample costs.
"""

from __future__ import annotations

import itertools

import numpy as np

SCORED = ("density_gb_mm2", "height_um", "cbl_ff", "margin_mv",
          "margin_disturbed_mv", "e_write_fj", "e_read_fj", "hcb_pitch_um",
          "blsa_area_um2")
TIMED = ("t_fire_ns", "t_sense_ns", "trc_ns", "margin_fire_mv")


def base_rows(spec) -> list:
    """(tech, scheme, layers) of every base row, entry-major."""
    return [(t, s, float(n)) for t, s, grid in spec["entries"] for n in grid]


def study_len(spec) -> int:
    n = len(base_rows(spec))
    for _, vals in spec["corners"]:
        n *= len(vals)
    if spec.get("mc"):
        n *= int(spec["mc"]["samples"])
    return n


def _mc_draws(config, spec, tech_of_row):
    """Monte-Carlo channels for every pre-MC row and sample -> (sa, dvth),
    each (samples, b0) float64, drawn with NumPy's PCG64 stream seeded by
    the spec's key: local i.i.d. draws first, then the die offset and the
    low-rank mat-gradient factors when `corr` > 0."""
    mc, techs = spec["mc"], config["techs"]
    samples, b0 = int(mc["samples"]), len(tech_of_row)
    corr = float(mc.get("corr", 0.0))
    gather = lambda k: np.asarray([techs[t][k] for t in tech_of_row], np.float64)
    rng = np.random.default_rng((int(mc["key"]),))
    z = rng.standard_normal((2, samples, b0))
    if corr > 0.0:
        k_fac = int(config["row_cycle"]["mc_gradient_factors"])
        f_die = corr * gather("mc_die_sigma_frac")
        f_mat = corr * gather("mc_mat_sigma_frac")
        z_die = rng.standard_normal((2, samples, 1))
        w_fac = rng.standard_normal((2, samples, k_fac))
        x = (np.arange(b0, dtype=np.float64) / max(b0 - 1, 1))[:, None]
        ell = np.maximum(gather("mc_corr_length"), 1e-3)[:, None]
        k = np.arange(k_fac, dtype=np.float64)[None, :]
        basis = np.sqrt(np.exp(-0.5 * (k * np.pi * ell) ** 2)) * np.cos(k * np.pi * x)
        basis = basis / np.maximum(np.sqrt((basis ** 2).sum(1, keepdims=True)), 1e-30)
        grad = np.einsum("csk,bk->csb", w_fac, basis)
        f_loc = np.maximum(1.0 - f_die - f_mat, 0.0)
        z = np.sqrt(f_loc) * z + np.sqrt(f_die) * z_die + np.sqrt(f_mat) * grad
    sa = np.maximum(gather("sa_offset_mv") + gather("sa_offset_sigma_mv") * z[0], 0.0)
    return sa, gather("vth_sigma_mv") * z[1]


def lower(config, spec, rows) -> dict:
    """Identity and input channels of the flat rows `rows` of a study."""
    rows = np.asarray(rows, np.int64)
    base = base_rows(spec)
    names = [n for n, _ in spec["corners"]]
    combos = list(itertools.product(*[v for _, v in spec["corners"]])) or [()]
    b, b0 = len(base), len(base) * len(combos)
    sample, rem = np.divmod(rows, b0)
    combo, i = np.divmod(rem, b)
    out = {"tech": [base[j][0] for j in i], "scheme": [base[j][1] for j in i],
           "layers": np.asarray([base[j][2] for j in i], np.float64),
           "corners": {n: np.asarray([combos[c][a] for c in combo], np.float64)
                       for a, n in enumerate(names)}}
    if spec.get("mc"):
        tech_pre = [base[j % b][0] for j in range(b0)]
        sa, dvth = _mc_draws(config, spec, tech_pre)
        out["corners"]["mc_sa_offset_mv"] = sa[sample, rem]
        out["corners"]["mc_delta_vth_mv"] = dvth[sample, rem]
    return out


class _Arith:
    """Elementwise arithmetic held to one precision: every constant and
    every input is cast to `dtype` before use, so bfloat16 stays bfloat16."""

    def __init__(self, dtype):
        self.dtype = dtype

    def __call__(self, x):
        return np.asarray(x, np.float64).astype(self.dtype)


def row_cycle(config, c, g, gc_res, gc_pre, v0, tau, thr, vdd, vpre,
              fire_from, replica, f):
    """Implicit-Euler ACT/RESTORE/PRE state machine on (N, M) ladders.

    `fire_from[m]` is the row whose ACT crossing fires row m's SA enable
    (itself, or its replica); `replica[m]` rows are ACT-only.  Returns the
    event columns t_dev, dv_sense, t_res, t_pre (NaN on a phase timeout).
    """
    rc = config["row_cycle"]
    dt = float(rc["dt_ns"])
    caps = (int(rc["act_steps"]), int(rc["restore_steps"]), int(rc["pre_steps"]))
    n, m = c.shape
    zero, one = f(0.0), f(1.0)
    cdt = c / f(dt) * f(1e-3)
    tau = np.maximum(tau, f(1e-3))
    phase = np.zeros(m, np.int64)
    tin = np.zeros(m, np.int64)
    v = v0.copy()
    evt = [np.full(m, np.nan, np.float64) for _ in range(4)]
    cap = np.asarray(caps, np.int64)
    restore_v = f(rc["restore_frac"]) * vdd
    tol = f(rc["equalize_tol_v"])
    for _ in range(sum(caps)):
        done = phase >= 3
        if done.all():
            break
        act, res, pre = phase == 0, phase == 1, phase == 2
        e = np.exp(-(f(tin + 1) * f(dt)) / tau)
        s = np.where(act, one - e, np.where(res, one, np.where(pre, e, zero)))
        gg = [g[i] for i in range(n - 2)] + [g[n - 2] * s]
        gc = [np.where(res, gc_res[i], np.where(pre, gc_pre[i], zero)) for i in range(n)]
        gcv = [np.where(res, gc_res[i] * vdd, np.where(pre, gc_pre[i] * vpre, zero))
               for i in range(n)]
        # Thomas solve of (C/dt + G + clamp) v' = C/dt v + clamp * target
        cp, dp = [], []
        for i in range(n):
            lo = gg[i - 1] if i > 0 else zero
            hi = gg[i] if i < n - 1 else zero
            diag = cdt[i] + lo + hi + gc[i]
            rhs = cdt[i] * v[i] + gcv[i]
            if i:
                diag = diag + lo * cp[i - 1]
                rhs = rhs + lo * dp[i - 1]
            cp.append(-hi / diag)
            dp.append(rhs / diag)
        x = [None] * n
        x[n - 1] = dp[n - 1]
        for i in range(n - 2, -1, -1):
            x[i] = dp[i] - cp[i] * x[i + 1]
        v = np.where(done, v, np.stack(x))

        dv = v[0] - vpre
        own = dv >= thr
        dev = np.max(np.abs(v[: n - 1] - vpre), axis=0)
        crossed = np.where(act, own[fire_from],
                           np.where(res, v[n - 1] >= restore_v, dev <= tol))
        tin1 = tin + 1
        advance = ~done & (crossed | (tin1 >= cap[np.minimum(phase, 2)]))
        t_evt = np.where(crossed, np.asarray(f(tin1 * dt), np.float64), np.nan)
        for ph in range(3):
            hit = advance & (phase == ph)
            evt[2 if ph == 1 else 3 if ph == 2 else 0][hit] = t_evt[hit]
            if ph == 0:
                evt[1][hit] = np.asarray(dv, np.float64)[hit]
        phase = np.where(advance, phase + np.where(replica, 3, 1), phase)
        tin = np.where(advance, 0, np.where(done, tin, tin1))
    return evt


def study_columns(config, spec, rows, dtype=np.float64) -> dict:
    """Scored columns of the flat rows `rows` of a study, as float64
    arrays (bool for the flags), computed in `dtype`."""
    f = _Arith(dtype)
    g0, techs, schemes = config["globals"], config["techs"], config["schemes"]
    low = lower(config, spec, rows)
    tk = lambda k: f([techs[t][k] for t in low["tech"]])
    tb = lambda k: np.asarray([bool(techs[t][k]) for t in low["tech"]])
    sk = lambda k: f([schemes[s][k] for s in low["scheme"]])
    sb = lambda k: np.asarray([bool(schemes[s][k]) for s in low["scheme"]])
    corner = {k: f(v) for k, v in low["corners"].items()}
    layers = f(low["layers"])
    zero = f(0.0)
    cs, vdd, vpre = f(g0["cs_ff"]), f(g0["vdd"]), f(g0["vbl_pre"])
    base2d = tb("baseline_2d")

    # bitline parasitics (fF, kOhm)
    c_vert = layers * tk("c_bl_per_layer_ff")
    c_local = np.where(base2d, tk("fixed_c_bl_ff") - tk("c_blsa_in_ff"),
                       c_vert + np.where(sb("sel_junction"), tk("c_sel_junction_ff"), zero))
    c_unsel = np.where(base2d, zero, (sk("straps_per_global") - f(1.0)) * c_vert)
    c_glob = np.where(base2d, zero,
                      np.where(sb("global_strap_metal"), tk("c_global_strap_ff"), zero)
                      + sk("c_global_fixed_ff") + tk("c_hcb_pad_ff"))
    c_sa = tk("c_blsa_in_ff")
    r_local = tk("r_local_bl_kohm")
    r_path = np.where(base2d, r_local,
                      r_local + np.where(sb("r_sel_in_path"), tk("r_sel_kohm"), zero)
                      + np.where(sb("r_global_in_path"), tk("r_global_kohm"), zero))
    r_on = tk("r_on_cell_kohm")
    if "mc_delta_vth_mv" in corner:
        vov = tk("vth_overdrive_v")
        dvth = np.clip(corner["mc_delta_vth_mv"] * f(1e-3), -f(0.5) * vov, f(0.5) * vov)
        r_on = r_on * vov / (vov - dvth)
    cbl = c_local + c_unsel + c_glob + c_sa

    # sensing ladder: sense node, K local-BL lumps, storage node
    k = int(config["row_cycle"]["bl_segments"])
    floor = f(0.05)
    c_lad = np.stack([c_glob + c_sa + c_unsel] + [c_local / f(k)] * k
                     + [cs + zero * layers])
    g_seg = f(1.0) / np.maximum(r_local / f(k), floor)
    g_lad = np.stack([f(1.0) / np.maximum(r_path - r_local, floor)] + [g_seg] * (k - 1)
                     + [f(1.0) / r_on])
    store = tk("writeback_eff") * vdd
    kernel = [(c_lad, g_lad, store)]
    if spec.get("replica"):
        cells = tk("replica_cells")
        c_rep, g_rep = c_lad.copy(), g_lad.copy()
        c_rep[-1] = c_rep[-1] * cells
        g_rep[-1] = g_rep[-1] * cells
        kernel = [(c_rep, g_rep, tk("replica_store_frac") * vdd)] + kernel
    nrow = layers.shape[0]
    cat = lambda xs: np.concatenate(xs, axis=-1)
    c_all = cat([x[0] for x in kernel])
    g_all = cat([x[1] for x in kernel])
    store_all = cat([x[2] for x in kernel])
    n = c_all.shape[0]
    v0 = np.stack([vpre + zero * store_all] * (n - 1) + [store_all])
    cbl_lad = c_all[: n - 1].sum(0, dtype=dtype)
    act_frac = f(config["row_cycle"]["act_frac"])
    thr = act_frac * ((store_all - vpre) * c_all[-1] / (c_all[-1] + cbl_lad))
    gc_res = np.zeros_like(c_all)
    gc_res[0] = f(1.0) / cat([tk("r_sa_drive_kohm")] * len(kernel))
    gc_pre = np.zeros_like(c_all)
    gc_pre[: n - 1] = f(1.0) / cat([tk("r_pre_kohm")] * len(kernel))
    tau = cat([tk("r_wl_kohm") * tk("c_wl_ff") * f(1e-3)] * len(kernel))
    idx = np.arange(nrow * len(kernel))
    fire_from = idx - nrow if len(kernel) == 2 else idx
    fire_from = np.where(idx < nrow * (len(kernel) - 1), idx, fire_from)
    replica = idx < nrow * (len(kernel) - 1)
    evt = row_cycle(config, c_all, g_all, gc_res, gc_pre, v0, tau, thr,
                    vdd + zero * tau, vpre + zero * tau, fire_from, replica, f)
    t_dev, dv, t_res, t_pre = (f(e[-nrow:]) for e in evt)

    # latch regeneration and the row-cycle roll-up
    half = vdd / f(2.0)
    t_regen = tk("sa_tau_ns") * np.log(np.maximum(half / np.maximum(dv, f(1e-4)), f(1.001)))
    t_sense = t_dev + t_regen
    trc = tk("t_overhead_ns") + t_sense + t_res + t_pre

    # scoring
    area = tk("cell_x_nm") * tk("cell_y_nm")
    per_layer = tk("array_efficiency") / np.where(area > zero, area, f(1.0)) * f(1e12) / f(1e9)
    density = np.where(base2d, tk("fixed_density_gb_mm2"), layers * per_layer)
    height = layers * tk("layer_height_nm") * f(1e-3)
    sa_off = corner.get("mc_sa_offset_mv", tk("sa_offset_mv"))
    margin = (f(1e3) * half * cs / (cs + cbl)
              - (f(1.0) - tk("writeback_eff")) * half * f(1e3) - sa_off)
    scale = layers / np.maximum(tk("layers_target"), f(1.0))
    duty_rh = corner.get("rh_toggles", f(g0["rh_toggles_per_64ms"])) / f(g0["rh_toggles_per_64ms"])
    duty_fbe = corner.get("trc_cycles", f(g0["trc_cycles_per_64ms"])) / f(g0["trc_cycles_per_64ms"])
    disturb = (tk("fbe_loss_mv") * scale * duty_fbe + tk("rh_loss_mv") * scale * duty_rh
               + np.where(sb("isolates_unselected") | base2d, zero,
                          f(15.0) * scale * duty_fbe))
    margin_d = margin - disturb
    eta = f(g0["energy_eff"])
    cbl_io = cbl + tk("c_route_extra_ff")
    e_write = f(0.5) * (cs + cbl_io) * vdd * vdd * eta
    e_read = f(0.5) * cbl_io * half * half * eta + tk("e_sa_fj")
    pitch = np.where(base2d, zero,
                     np.sqrt(tk("cell_x_nm") * f(1e-3) * tk("hcb_route_span_um"))
                     * np.where(sb("bond_shared"), np.sqrt(f(g0["bls_per_strap"])), f(1.0)))
    manufacturable = base2d | (pitch >= f(g0["hcb_min_pitch_um"]))
    margin_fire = dv * f(1e3) - sa_off
    feasible = (manufacturable
                & (margin >= f(g0["min_functional_margin_mv"]) - f(1e-9))
                & (margin_d >= f(g0["min_disturbed_margin_mv"]) - f(1e-9))
                & np.isfinite(np.asarray(trc, np.float64)))
    cols = dict(density_gb_mm2=density, height_um=height, cbl_ff=cbl,
                margin_mv=margin, margin_disturbed_mv=margin_d,
                e_write_fj=e_write, e_read_fj=e_read, hcb_pitch_um=pitch,
                blsa_area_um2=f(2.0) * pitch * pitch, t_fire_ns=t_dev,
                t_sense_ns=t_sense, trc_ns=trc, margin_fire_mv=margin_fire)
    out = {k: np.asarray(v, np.float64) for k, v in cols.items()}
    out.update(tech=low["tech"], scheme=low["scheme"],
               layers=np.asarray(layers, np.float64),
               corners={k2: np.asarray(v2, np.float64) for k2, v2 in corner.items()},
               manufacturable=np.asarray(manufacturable, bool),
               feasible=np.asarray(feasible, bool),
               valid=np.ones(nrow, bool),
               t_overhead_ns=np.asarray(tk("t_overhead_ns"), np.float64))
    return out


def sampled_columns(config, picks, dtype=np.float64) -> dict:
    """Columns of every (spec, rows) pick, joined in order."""
    from .compare import concat
    return concat([study_columns(config, spec, rows, dtype) for spec, rows in picks])
