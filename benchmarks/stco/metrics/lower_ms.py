"""Host lowering (`dse.plan_sweep`, `DesignSpace.lower`): self time of the
harness's `stco.plan` span, mean per study, in ms."""

from benchmarks.stco import trace


def read(run):
    plans = trace.spans_named(run.trace, "stco.plan")
    if not plans:
        return None
    return sum(e - s for _, s, e in plans) / len(plans) * 1e-6
