"""The comparison that decides `correct`, at a size a CPU test run holds:
the bfloat16 control fails every cell's limits while float32 arithmetic
passes them, and a run whose timed path is broken underneath comes out
not correct — state left unchanged, half of the rows left out, an answer
altered where it is produced."""

import argparse

import ml_dtypes
import numpy as np
import pytest

from benchmarks.stco import bench, compare, harness, reference, spaces
from conftest import fake_tpu

CELLS = ("signoff.batch",)


def _picks(cell, seed):
    """A study spec of the cell's configuration and a sample of its rows."""
    spec = spaces.study_spec(cell.config, dict(cell.mix, mc_samples=16), seed, 0)
    n = reference.study_len(spec)
    rows = np.sort(np.random.default_rng(seed).choice(n, min(n, 384), replace=False))
    return [(spec, rows)]


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_control_fails_and_float32_passes(name):
    cell = harness.load_cell(name)
    cfg, picks = cell.config, _picks(cell, 2**34 + 5)
    ref = reference.sampled_columns(cfg, picks)
    low = reference.sampled_columns(cfg, picks, dtype=ml_dtypes.bfloat16)
    same = reference.sampled_columns(cfg, picks, dtype=np.float32)
    ok_low, checks = compare.judge(compare.numbers(low, ref, cfg), cell.limits)
    assert not ok_low, checks
    ok_same, checks = compare.judge(compare.numbers(same, ref, cfg), cell.limits)
    assert ok_same, checks
    ok_self, _ = compare.judge(compare.numbers(ref, ref, cfg), cell.limits)
    assert ok_self


# Faults of the engine's output, each `fault(evt, v_end, args)` with the
# engine's operands `args` (c, g, gc_res, gc_pre, v0, params).

def state_unchanged(evt, v_end, args):
    import jax.numpy as jnp
    frozen = jnp.full((evt.shape[0], 4), jnp.nan, jnp.float32).at[:, 1].set(0.0)
    return frozen, args[4]


def half_left_out(evt, v_end, args):
    live = int((np.asarray(args[5])[:, 4] > 0.5).sum())   # rows past it are padding
    h = max((live // 2) & ~1, 2)                          # [replica, main] pairs whole
    # each row past the first half takes the answer of a first-half row,
    # pairs in reverse order: another design, not a copy of its own
    i = np.arange(evt.shape[0])
    back = h // 2 - 1 - (i // 2 - h // 2) % (h // 2)
    src = np.where(i < h, i, 2 * back + i % 2)
    return evt[src], v_end[src]


def answer_altered(evt, v_end, args):
    return evt.at[:, 0].add(0.02), v_end       # every SA enable one step late


FAULTS = {f.__name__: f for f in (state_unchanged, half_left_out, answer_altered)}


def plant(monkeypatch, fault):
    """Break the per-chunk kernel call that the window's engine makes
    with `fault`."""
    from repro.kernels import ops
    kernel = ops.row_cycle_fused

    def broken(*args, **kw):
        evt, v_end = kernel(*args, **kw)
        return fault(evt, v_end, args)
    monkeypatch.setattr(ops, "row_cycle_fused", broken)


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(monkeypatch, small_cell, name, fault):
    cell = small_cell(name)
    args = argparse.Namespace(workload=name, seed=2**37 + 3, seconds=0.3, trace=0)
    plant(monkeypatch, FAULTS[fault])
    out = bench.run(args, cell=cell, device_check=fake_tpu, compile_cache=False)
    assert out["correct"] is False, out["checks"]
