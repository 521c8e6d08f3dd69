"""Dispatch and chunking: row-cycle kernel executions in the device trace,
per study and per device."""

from benchmarks.stco import trace


def read(run):
    studies = trace.spans_named(run.trace, "stco.study")
    counts = [len(trace.matching(run.trace, d, trace.ROW_CYCLE_OPS, s, e))
              for _, s, e in studies for d in run.devices]
    if not counts or not sum(counts):
        return None
    return sum(counts) / len(counts)
