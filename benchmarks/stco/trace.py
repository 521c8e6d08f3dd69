"""Reduction of a profiler trace (`.xplane.pb`) to device and host facts.

Device planes are `/device:TPU:<n>`; their "XLA Ops" line holds one event
per operation run on the chip, named by its HLO instruction (`%name = ...`,
kept here as `name`), and their "XLA Modules" line one event per program
run (`jit_<function>(...)`).  The harness's own spans
(`jax.profiler.TraceAnnotation`, names starting `stco.`) lie on the host
threads, on the same clock.  The traced window is the `stco.window` span.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field

# Names the trace gives the programs the per-layer metrics read, matched
# as substrings: the row-cycle kernel is the `row_cycle_fused` custom call
# among the ops; scoring is the `jit_score_from_events` module.  The
# program gives neither a stable name yet.
ROW_CYCLE_OPS = ("row_cycle",)
SCORING_MODULES = ("score_from_events",)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)      # device -> [(name, start, end)] ns
    spans: list = field(default_factory=list)    # host [(name, start, end)] ns
    window: tuple = (0.0, 0.0)
    modules: dict = field(default_factory=dict)  # device -> [(name, start, end)] ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def op_name(hlo: str) -> str:
    """`%row_cycle_fused.1 = (f32[...]) custom-call(...)` -> `row_cycle_fused.1`."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def from_events(ops: dict, spans: list, modules: dict | None = None) -> Trace:
    """A `Trace` from plain event lists (what `load` reads; tests build
    small ones by hand)."""
    win = [s for s in spans if s[0] == "stco.window"]
    window = (win[0][1], win[0][2]) if win else (
        min(s[1] for s in spans), max(s[2] for s in spans))
    clip = lambda evs: sorted(e for e in evs if e[2] > window[0] and e[1] < window[1])
    return Trace(ops={d: clip(evs) for d, evs in ops.items()},
                 spans=sorted(spans, key=lambda s: s[1]), window=window,
                 modules={d: clip(evs) for d, evs in (modules or {}).items()})


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops, spans, modules = {}, [], {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                ops.setdefault(int(m.group(1)), []).extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif m and line.name == "XLA Modules":
                modules.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events)
    return from_events(ops, spans, modules)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals, clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace, device: int) -> float:
    return union_ns(trace.ops.get(device, []), *trace.window) * 1e-9


def matching(trace: Trace, device: int, patterns, lo=None, hi=None,
             line: str = "ops") -> list:
    """Ops (or, with line="modules", programs) of one device whose name
    holds any of `patterns`, optionally only those starting in [lo, hi)."""
    lo = trace.window[0] if lo is None else lo
    hi = trace.window[1] if hi is None else hi
    return [e for e in getattr(trace, line).get(device, [])
            if lo <= e[1] < hi and any(p in e[0] for p in patterns)]


def spans_named(trace: Trace, name: str) -> list:
    return [s for s in trace.spans if s[0] == name]


def per_study_device_ms(run, patterns, line: str = "ops") -> list:
    """Per `stco.study` span: device time (ms) of the matching ops (or
    programs) on the slowest of the run's devices; [] where none match."""
    out = []
    for _, s, e in spans_named(run.trace, "stco.study"):
        out.append(max(sum(b - a for _, a, b in matching(run.trace, d, patterns, s, e, line))
                       for d in run.devices) * 1e-6)
    return out if any(out) else []


def top_ops(trace: Trace, k: int = 10) -> list:
    """[[name, seconds], ...]: device time by op name, summed over the
    devices' ops inside the window and averaged over devices."""
    tot: dict = {}
    for evs in trace.ops.values():
        for name, s, e in evs:
            base = re.sub(r"\.\d+$", "", name)
            tot[base] = tot.get(base, 0.0) + (e - s) * 1e-9
    n = max(len(trace.ops), 1)
    return [[name, sec / n] for name, sec in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace: Trace, device: int = 0, k: int = 10) -> list:
    """[[host activity, seconds], ...]: the longest stretches with nothing
    running on `device`, each named by the innermost host event that
    covers its midpoint."""
    lo, hi = trace.window
    gaps, cur = [], lo
    for _, s, e in sorted(trace.ops.get(device, []), key=lambda x: x[1]):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (s + e)
        cover = [sp for sp in trace.spans if sp[1] <= mid < sp[2]]
        name = min(cover, key=lambda sp: sp[2] - sp[1])[0] if cover else "no host event"
        out.append([name, (e - s) * 1e-9])
    return out
