"""Fused row-cycle kernel: least time the chips could take for the
study's useful work (`roofline.study_work`, published peaks) over the
kernel's device time on the slowest device, in %, mean per study."""

from benchmarks.stco import roofline, trace


def read(run):
    per_study = trace.per_study_device_ms(run, trace.ROW_CYCLE_OPS)
    if not per_study or len(per_study) != len(run.loop.work):
        return None
    n = len(run.devices)
    shares = [100.0 * roofline.bound(ops / n, nbytes / n, run.peak)[0] / (ms * 1e-3)
              for (ops, nbytes), ms in zip(run.loop.work, per_study)]
    return sum(shares) / len(shares)
