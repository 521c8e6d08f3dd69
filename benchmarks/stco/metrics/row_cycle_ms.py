"""Fused row-cycle kernel: summed device time of its executions, per
study, on the slowest device, in ms."""

from benchmarks.stco import trace


def read(run):
    per_study = trace.per_study_device_ms(run, trace.ROW_CYCLE_OPS)
    return sum(per_study) / len(per_study) if per_study else None
