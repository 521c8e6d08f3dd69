"""The comparison that decides `correct`: program columns against the
plain reference, row by row, reduced to a few named numbers.

Every number is 0 for identical columns and grows with the disagreement;
each is held to its own limit in `limits/<workload>.json`.

- input_gap           widest gap of the layer count and of a corner channel
                      (disturb duty, MC draws); infinite where a row's tech,
                      scheme or validity differs or a channel is missing
- static_gap          widest gap of a scored column that needs no transient
- fire_step_off_share share of rows whose SA-enable step differs (a phase
                      timeout on one side only counts as differing)
- event_step_gap      most integration steps between the two event times
                      (SA enable; restore + precharge), rows where both
                      sides have the event
- fire_margin_gap     median gap of the margin at SA enable (the widest is
                      set by rows that fire one step apart, which rounding
                      decides; the median reads the arithmetic of them all)
- timing_gap          widest gap of t_fire, t_sense and tRC, rows where both
                      sides have them
- flag_mismatch       rows whose feasible/manufacturable flag or whose
                      phase-timeout (NaN) pattern differs, away from the
                      margin thresholds; an exact comparison

A gap is |program - reference| / max(|reference|, median |reference| of
the column), so a value near zero is not divided by itself.
"""

from __future__ import annotations

import numpy as np

from . import reference

NUMBERS = ("input_gap", "static_gap", "fire_step_off_share", "event_step_gap",
           "fire_margin_gap", "timing_gap", "flag_mismatch")
# margin within this many mV of a feasibility threshold: a flag that
# flips there is rounding, not a wrong answer
THRESHOLD_BAND_MV = 1e-2


def _gap(p, r) -> np.ndarray:
    p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
    ok = np.isfinite(p) & np.isfinite(r)
    if not ok.any():
        return np.zeros(0)
    floor = np.median(np.abs(r[ok]))
    return np.abs(p[ok] - r[ok]) / np.maximum(np.abs(r[ok]), max(floor, 1e-30))


def _widest(*gaps) -> float:
    return float(max((g.max() for g in gaps if g.size), default=0.0))


def numbers(prog: dict, ref: dict, config) -> dict:
    """The compared numbers for one set of rows (dicts of columns as
    `reference.study_columns` returns them)."""
    dt = float(config["row_cycle"]["dt_ns"])
    g0 = config["globals"]
    f64 = lambda d, k: np.asarray(d[k], np.float64)
    ident = ((np.asarray(prog["tech"]) != np.asarray(ref["tech"]))
             | (np.asarray(prog["scheme"]) != np.asarray(ref["scheme"]))
             | (np.asarray(prog["valid"]) != np.asarray(ref["valid"])))
    keys = sorted(set(prog["corners"]) | set(ref["corners"]))
    missing = [k for k in keys if k not in prog["corners"] or k not in ref["corners"]]
    input_gap = np.inf if missing or ident.any() else _widest(
        _gap(prog["layers"], ref["layers"]),
        *[_gap(prog["corners"][k], ref["corners"][k]) for k in keys])

    cols = reference.SCORED + reference.TIMED
    nan_rows = np.zeros(len(ident), bool)
    for k in cols:
        nan_rows |= np.isnan(f64(prog, k)) != np.isnan(f64(ref, k))

    steps = lambda x: np.rint(x / dt)
    fire_p, fire_r = steps(f64(prog, "t_fire_ns")), steps(f64(ref, "t_fire_ns"))
    rest = lambda d: steps(f64(d, "trc_ns") - f64(ref, "t_overhead_ns") - f64(d, "t_sense_ns"))
    rest_p, rest_r = rest(prog), rest(ref)
    fire_ok = np.isfinite(fire_p) & np.isfinite(fire_r)
    rest_ok = np.isfinite(rest_p) & np.isfinite(rest_r)
    fire_off = np.where(fire_ok, np.abs(fire_p - fire_r), 0.0)
    rest_off = np.where(rest_ok, np.abs(rest_p - rest_r), 0.0)
    fire_differs = (fire_off >= 1) | (np.isnan(fire_p) != np.isnan(fire_r))

    margins = np.stack([f64(ref, "margin_mv") - g0["min_functional_margin_mv"],
                        f64(ref, "margin_disturbed_mv") - g0["min_disturbed_margin_mv"]])
    near = (np.abs(margins) <= THRESHOLD_BAND_MV).any(axis=0)
    flags = ((np.asarray(prog["feasible"]) != np.asarray(ref["feasible"]))
             | (np.asarray(prog["manufacturable"]) != np.asarray(ref["manufacturable"])))
    margin_fire = _gap(prog["margin_fire_mv"], ref["margin_fire_mv"])
    return {
        "input_gap": float(input_gap),
        "static_gap": _widest(*[_gap(prog[k], ref[k]) for k in reference.SCORED]),
        "fire_step_off_share": float(fire_differs.mean()) if len(fire_differs) else 0.0,
        "event_step_gap": float(max(fire_off.max(initial=0.0), rest_off.max(initial=0.0))),
        "fire_margin_gap": float(np.median(margin_fire)) if margin_fire.size else 0.0,
        "timing_gap": _widest(*[_gap(prog[k], ref[k])
                                for k in ("t_fire_ns", "t_sense_ns", "trc_ns")]),
        "flag_mismatch": float(((flags | nan_rows) & ~near).sum()),
    }


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, checks): each number beside its limit; a number that is
    missing, NaN or over its limit fails."""
    checks, ok = {}, True
    for name in NUMBERS:
        v, lim = values.get(name), limits[name]
        good = v is not None and np.isfinite(v) and v <= lim
        ok &= bool(good)
        checks[name] = {"value": v, "limit": lim}
    return ok, checks


def concat(parts: list) -> dict:
    """Join column dicts of several row sets into one."""
    out = {}
    for k in parts[0]:
        if k == "corners":
            out[k] = {c: np.concatenate([p[k][c] for p in parts]) for c in parts[0][k]}
        elif k == "t_overhead_ns" or isinstance(parts[0][k], np.ndarray):
            out[k] = np.concatenate([np.asarray(p[k]) for p in parts])
        else:
            out[k] = [x for p in parts for x in p[k]]
    return out
