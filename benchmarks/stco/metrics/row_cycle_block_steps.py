"""Fused row-cycle kernel: the kernel's own step count, summed over its
batch blocks (`block_steps` of `engine.dispatch`), per study and per chip.
Each block steps until its slowest row is DONE."""

from benchmarks.stco import program


def read(run):
    per_study = program.block_steps(run)
    if not per_study:
        return None
    return sum(c["block_steps"] for c in per_study) / len(per_study) / len(run.devices)
