"""Scoring (`dse.score_from_events`): device time of the scoring program
(its XLA module), per study, on the slowest device, in ms."""

from benchmarks.stco import trace


def read(run):
    per_study = trace.per_study_device_ms(run, trace.SCORING_MODULES, line="modules")
    return sum(per_study) / len(per_study) if per_study else None
