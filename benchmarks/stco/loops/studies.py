"""Loop kind "studies": whole studies back to back, each from a
`DesignSpace` to a scored `DesignBatch` on the host through `dse.sweep`.
A study that starts inside the window is finished and counted.

A loop kind is a file `loops/<kind>.py` with a class `Loop(cell, seed,
traced)`: `setup(seconds)` warms every shape its window runs,
`window(seconds)` measures, `end_to_end()` gives the host-clock metrics,
`attempted` and `failed` count the window's work, and `sample(rng)` gives
the program's columns for a sample of the window's answers drawn from the
seed, with the study specs and rows the reference recomputes
(`reference.sampled_columns`).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.stco import compare, roofline, spaces
from benchmarks.stco.harness import program_columns

WARM_STUDY = (1 << spaces.STUDY_KEY_BITS) - 1     # study index used to warm up


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Loop:
    failed = 0                     # a study that raises ends the run with no result

    def __init__(self, cell, seed: int, traced: bool):
        self.cell, self.seed, self.traced = cell, int(seed), traced
        self.mix = cell.mix
        self.studies = []          # (spec, batch) per study of the window
        self.t0 = self.t_end = 0.0
        self.work = []             # (ops, bytes) per traced study

    def _study(self, spec):
        import jax
        from repro.core import dse, transient
        space = spaces.design_space(spec)
        if not self.traced:
            # every column of the scored batch, not only the first ready one
            return jax.block_until_ready(dse.sweep(space))
        # the steps dse.sweep runs, in order, each in a span of its own
        with _annotate("stco.study"):
            with _annotate("stco.plan"):
                plan = dse.plan_sweep(space)
                jax.block_until_ready(plan.operands)
            with _annotate("stco.engine"):
                res = transient.simulate_row_cycle_many(plan.operands)
                jax.block_until_ready(res.events)
            with _annotate("stco.finalize"):
                batch = dse.finalize_sweep(plan, res)
            jax.block_until_ready(batch)
        return batch

    def setup(self, seconds: float) -> None:
        self._study(spaces.study_spec(self.cell.config, self.mix, self.seed, WARM_STUDY))

    def window(self, seconds: float) -> None:
        self.t0 = time.perf_counter()
        i = 0
        while i == 0 or time.perf_counter() - self.t0 < seconds:
            spec = spaces.study_spec(self.cell.config, self.mix, self.seed, i)
            self.studies.append((spec, self._study(spec)))
            self.t_end = time.perf_counter()
            i += 1
        if self.traced:
            self.work = [roofline.study_work(self.cell.config, spec, batch)
                         for spec, batch in self.studies]

    @property
    def attempted(self) -> int:
        return len(self.studies)

    def end_to_end(self) -> dict:
        rows = sum(len(b) for _, b in self.studies)
        return {"design_rows_per_s": rows / (self.t_end - self.t0)}

    def sample(self, rng) -> tuple[dict, list]:
        """Program columns of `sample_rows` rows drawn from all the
        window's studies, and the (spec, rows) they came from; then frees
        the program's answers."""
        n = int(self.mix["sample_rows"])
        sizes = np.asarray([len(b) for _, b in self.studies])
        flat = np.sort(rng.choice(int(sizes.sum()), n, replace=False))
        which = np.searchsorted(np.cumsum(sizes), flat, side="right")
        starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
        picks = [(self.studies[j][0], flat[which == j] - starts[j]) for j in np.unique(which)]
        prog = compare.concat([program_columns(self.studies[j][1], rows)
                               for j, (_, rows) in zip(np.unique(which), picks)])
        self.studies = [(spec, None) for spec, _ in self.studies]
        return prog, picks
