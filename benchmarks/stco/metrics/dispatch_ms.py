"""Dispatch and chunking (`transient._row_cycle_fused_chunked`): duration
of the program's `engine.dispatch` span, per study, in ms: the host's time
to pad, slice and enqueue every chunk.  The loop reads no device value, so
where this reads as long as `row_cycle_ms` the runtime is holding the
enqueue for the kernels in flight."""

from benchmarks.stco import program


def read(run):
    per_study = program.span_ms(run, "engine.dispatch")
    return sum(per_study) / len(per_study) if per_study else None
