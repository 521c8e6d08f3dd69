"""The program's own spans and counters (`repro.obs`) in a traced run, per
study.

Spans are read from the profiler trace (`run.trace.spans`), where each
`obs.span` lies as a host event of its name.  Counters are read from
`repro.obs.records()`.  Both are on CLOCK_REALTIME, but a `.xplane.pb`
counts its events from the session's `profile_start_time`, which
`trace.load` does not keep.  So the records of each span name are paired,
latest first, with the trace's events of that name (the trace holds every
span of the traced window, and no span runs after it before the readers),
and the median offset of the pairs puts the records on the trace's clock.
Records are then kept inside the window, each in the `stco.study` span
that holds its start.  A program without `repro.obs` (or with no span in
the trace) gives nothing, and so does a pairing whose offsets disagree.
"""

from __future__ import annotations

import statistics

from benchmarks.stco import trace

# pairs whose offsets differ by more than this are not the same spans
PAIRING_TOLERANCE_NS = 1_000_000


def _studies(run) -> list:
    return [(s, e) for _, s, e in trace.spans_named(run.trace, "stco.study")]


def _study_of(studies, t) -> int | None:
    for i, (s, e) in enumerate(studies):
        if s <= t < e:
            return i
    return None


def span_ms(run, name: str) -> list:
    """Per `stco.study`: summed duration (ms) of the trace's `name` spans
    that start in it; [] where the trace has none."""
    studies = _studies(run)
    out = [0.0] * len(studies)
    found = False
    for _, s, e in trace.spans_named(run.trace, name):
        i = _study_of(studies, s)
        if i is not None:
            out[i] += (e - s) * 1e-6
            found = True
    return out if found else []


def records(run) -> list:
    """[(record, start on the trace's clock)] of the obs records that lie
    inside the traced window; [] where they cannot be placed."""
    try:
        from repro import obs
    except ImportError:
        return []
    recs = obs.records()
    offsets = []
    for name in {r.name for r in recs}:
        starts = sorted(s for _, s, _ in trace.spans_named(run.trace, name))
        mine = sorted(r.start_ns for r in recs if r.name == name)
        if starts and len(mine) >= len(starts):
            offsets += [r - s for r, s in zip(mine[-len(starts):], starts)]
    if not offsets:
        return []
    offset = statistics.median(offsets)
    if max(abs(o - offset) for o in offsets) > PAIRING_TOLERANCE_NS:
        return []
    lo, hi = run.trace.window
    return [(r, r.start_ns - offset) for r in recs if lo <= r.start_ns - offset < hi]


def counters(run, span: str) -> list:
    """Per `stco.study`: the counters of its `span` records, summed, or
    None where it has none; [] where no `span` record lies in the window."""
    studies = _studies(run)
    out = [None] * len(studies)
    for rec, start in records(run):
        i = _study_of(studies, start) if rec.name == span else None
        if i is not None:
            total = out[i] = out[i] or {}
            for k, v in rec.counters.items():
                total[k] = total.get(k, 0) + v
    return out if any(c is not None for c in out) else []


def block_steps(run) -> list:
    """Per `stco.study`: the `engine.dispatch` counters, where every study
    of the window has its kernel's `block_steps`; else []."""
    per_study = counters(run, "engine.dispatch")
    if any(c is None or "block_steps" not in c for c in per_study):
        return []
    return per_study
