"""Spans and counters of the sweep, always on.

    with obs.span("engine.dispatch"):
        ...
        obs.count("launches", n)

A span enters a `jax.profiler.TraceAnnotation` of its name, so under a
profiler session it lies in the trace beside the device ops it enqueued.
On exit it appends a `Record` to an in-memory buffer that keeps the last
`BUFFER_RECORDS` records.  A record holds the span's name, its start and end
(`time.time_ns()`, CLOCK_REALTIME: the profiler's clock), the id of the span
that was open around it in the same thread (`parent`), the id of the
outermost one (`root`), and its counters.

`count` adds to the innermost open span of the calling thread (outside any
span it is dropped).  A value may be a device scalar: it is kept as given
and converted only when a reader asks, so counting never waits for the
device, and reading a counter given as a device scalar waits for the work
that made it.  JAX's compile events are counted on the innermost open span
too (`compiles`, `compile_s`, `cache_loads`), and in process totals.

`records()` and `summary()` are the readers; nothing is written out.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field

import jax
import numpy as np

BUFFER_RECORDS = 4096
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclass
class Record:
    """One finished span."""
    name: str
    id: int
    parent: int | None
    root: int
    start_ns: int
    end_ns: int = 0
    child_ns: int = 0              # summed duration of its direct children
    values: dict = field(default_factory=dict)   # counter -> [values as given]

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def self_ns(self) -> int:
        """Time in this span outside its children."""
        return self.duration_ns - self.child_ns

    @property
    def counters(self) -> dict:
        """Counter totals as Python numbers; a total of device scalars
        waits for them once, then is kept."""
        for name, vals in self.values.items():
            if len(vals) != 1 or not isinstance(vals[0], (int, float)):
                self.values[name] = [sum(np.asarray(v).item() for v in vals)]
        return {name: vals[0] for name, vals in self.values.items()}


_records: collections.deque = collections.deque(maxlen=BUFFER_RECORDS)
_ids = itertools.count(1)
_local = threading.local()
_compile_lock = threading.Lock()
_compile = {"programs": 0, "seconds": 0.0, "cache_loads": 0}


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


@contextlib.contextmanager
def span(name: str):
    """Time the block as a span named `name`, nested in the calling
    thread's open span, if any."""
    stack = _stack()
    parent = stack[-1] if stack else None
    rid = next(_ids)
    with jax.profiler.TraceAnnotation(name):
        rec = Record(name, rid, parent.id if parent else None,
                     parent.root if parent else rid, time.time_ns())
        stack.append(rec)
        try:
            yield
        finally:
            rec.end_ns = time.time_ns()
            stack.pop()
            if parent is not None:
                parent.child_ns += rec.duration_ns
            _records.append(rec)


def count(name: str, value) -> None:
    """Add `value` (a number or a device scalar) to counter `name` of the
    calling thread's innermost open span."""
    stack = _stack()
    if stack:
        stack[-1].values.setdefault(name, []).append(value)


def records() -> list:
    """The buffered records, oldest first."""
    return list(_records)


def summary() -> dict:
    """Per span name: count, total and self time (ms) and summed counters
    over the buffered records; and the process's compile totals since
    this module was imported (`programs`, `seconds`, `cache_loads`)."""
    spans: dict = {}
    for rec in records():
        s = spans.setdefault(rec.name, {"count": 0, "total_ms": 0.0,
                                        "self_ms": 0.0, "counters": {}})
        s["count"] += 1
        s["total_ms"] += rec.duration_ns * 1e-6
        s["self_ms"] += rec.self_ns * 1e-6
        for k, v in rec.counters.items():
            s["counters"][k] = s["counters"].get(k, 0) + v
    with _compile_lock:
        compiles = dict(_compile)
    return {"spans": spans, "compile": compiles}


def _on_duration(event: str, duration: float, **_) -> None:
    if event == COMPILE_EVENT:
        with _compile_lock:
            _compile["programs"] += 1
            _compile["seconds"] += duration
        count("compiles", 1)
        count("compile_s", duration)


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        with _compile_lock:
            _compile["cache_loads"] += 1
        count("cache_loads", 1)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
