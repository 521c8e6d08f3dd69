"""Host lowering (`dse.plan_sweep`): duration of the program's `dse.plan`
span, per study, in ms.  The inside counterpart of `lower_ms`, without
the harness's wait for the lowered operands."""

from benchmarks.stco import program


def read(run):
    per_study = program.span_ms(run, "dse.plan")
    return sum(per_study) / len(per_study) if per_study else None
