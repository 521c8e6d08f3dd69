"""Fused row-cycle kernel: useful row-steps (`roofline.study_work`
operations over `roofline.OPS_PER_STEP`) over the row-steps the kernel
ran (`block_steps` of `engine.dispatch` times the rows of a block, as the
program blocks a launch of `rows_padded / launches` rows), in %, mean per
study."""

from benchmarks.stco import program, roofline


def read(run):
    per_study = program.block_steps(run)
    if not per_study or len(per_study) != len(run.loop.work):
        return None
    from repro.kernels import ops
    shares = []
    for (work_ops, _), c in zip(run.loop.work, per_study):
        block = ops.row_cycle_block_rows(c["rows_padded"] // c["launches"])
        shares.append(100.0 * work_ops / roofline.OPS_PER_STEP / (c["block_steps"] * block))
    return sum(shares) / len(shares)
