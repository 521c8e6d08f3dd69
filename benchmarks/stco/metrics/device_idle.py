"""Device: share of the traced window in which no operation ran on the
chip, in %, mean over the chips used."""

from benchmarks.stco import trace


def read(run):
    if run.trace.window_s <= 0 or not any(run.trace.ops.get(d) for d in run.devices):
        return None
    busy = sum(trace.busy_s(run.trace, d) for d in run.devices) / len(run.devices)
    return 100.0 * (1.0 - busy / run.trace.window_s)
