"""Readers of the program's own spans and counters (`repro.obs`): on a
synthetic trace with seeded records, and on the trace of a small CPU run."""

import argparse
import sys

import jax.numpy as jnp
import pytest

from benchmarks.stco import bench, harness, peaks, roofline, trace
from conftest import fake_tpu

from repro import obs
from repro.kernels import ops

NEW = ("plan_ms", "dispatch_ms", "row_cycle_block_steps", "row_cycle_step_efficiency")
MS = 1_000_000
# the trace counts from the session's start; records hold CLOCK_REALTIME
SESSION_START = 1_792_000_000_000_000_000


def _spans():
    return [("stco.window", 0, 100 * MS),
            ("stco.study", 0, 50 * MS), ("stco.plan", 0, 10 * MS),
            ("dse.plan", 1 * MS, 9 * MS), ("engine.dispatch", 11 * MS, 13 * MS),
            ("stco.study", 50 * MS, 100 * MS), ("stco.plan", 50 * MS, 56 * MS),
            ("dse.plan", 51 * MS, 55 * MS), ("engine.dispatch", 57 * MS, 58.5 * MS)]


def _record(i, name, start, end, **counters):
    """A record of a span that ran `start`..`end` on the trace's clock,
    read a few microseconds later than its trace event, as the obs clock is."""
    t0 = SESSION_START + start + 3_000 + 500 * i
    return obs.Record(name, i, None, i, t0, t0 + end - start,
                      values={k: [v] for k, v in counters.items()})


def _records():
    return [
        # the warm-up study, before the traced window
        _record(1, "dse.plan", -900 * MS, -890 * MS),
        _record(2, "engine.dispatch", -880 * MS, -870 * MS, block_steps=999_999),
        _record(3, "dse.plan", 1 * MS, 9 * MS),
        _record(4, "engine.dispatch", 11 * MS, 13 * MS, launches=2, rows_padded=4096,
                block_steps=jnp.int32(4000)),
        _record(5, "dse.plan", 51 * MS, 55 * MS),
        _record(6, "engine.dispatch", 57 * MS, 58.5 * MS, launches=2, rows_padded=4096,
                block_steps=jnp.int32(3000)),
    ]


def _run(records=_records, spans=_spans, devices=(0,)):
    run = argparse.Namespace(
        trace=trace.from_events({}, spans()), devices=list(devices),
        loop=argparse.Namespace(work=[(2.0e6 * roofline.OPS_PER_STEP, 1e6),
                                      (1.2e6 * roofline.OPS_PER_STEP, 1e6)]),
        peak=peaks.peaks("TPU v5 lite"))
    return run, records


@pytest.fixture()
def read(monkeypatch):
    def go(name, run_and_records):
        run, records = run_and_records
        monkeypatch.setattr(obs, "records", records)
        return harness.metric_reader(name)(run)
    return go


def test_readers_on_a_synthetic_trace_of_two_studies(read):
    assert read("plan_ms", _run()) == pytest.approx((8 + 4) / 2)
    assert read("dispatch_ms", _run()) == pytest.approx((2 + 1.5) / 2)
    # the warm-up record (999,999 steps) lies before the window
    assert read("row_cycle_block_steps", _run()) == pytest.approx((4000 + 3000) / 2)
    # rows of a block as the program blocks a 2,048-row launch
    block = ops.row_cycle_block_rows(2048)
    want = (100 * 2.0e6 / (4000 * block) + 100 * 1.2e6 / (3000 * block)) / 2
    assert read("row_cycle_step_efficiency", _run()) == pytest.approx(want)


def test_block_steps_are_per_chip(read):
    assert read("row_cycle_block_steps", _run(devices=(0, 1))) == pytest.approx(3500 / 2)


def test_readers_give_nothing_without_spans_or_records(read, monkeypatch):
    no_spans = lambda: [("stco.window", 0, 100 * MS)]
    no_records = lambda: []
    for name in NEW:
        assert read(name, _run(spans=no_spans)) is None
    for name in ("row_cycle_block_steps", "row_cycle_step_efficiency"):
        assert read(name, _run(records=no_records)) is None
    # a program without repro.obs (the parent of this reader) gives nothing
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in ("row_cycle_block_steps", "row_cycle_step_efficiency"):
        assert harness.metric_reader(name)(_run()[0]) is None


def test_records_that_do_not_pair_with_the_trace_give_nothing(read):
    # the second study's dispatch record is missing: the latest records no
    # longer line up with the trace's spans of that name
    broken = lambda: [r for r in _records() if r.id != 6]
    assert read("row_cycle_block_steps", _run(records=broken)) is None


def test_a_study_without_a_count_gives_no_efficiency(read):
    uncounted = lambda: _records()[:-1] + [
        _record(6, "engine.dispatch", 57 * MS, 58.5 * MS, launches=2, rows_padded=4096)]
    assert read("dispatch_ms", _run(records=uncounted)) == pytest.approx(1.75)
    assert read("row_cycle_block_steps", _run(records=uncounted)) is None
    assert read("row_cycle_step_efficiency", _run(records=uncounted)) is None


def test_readers_on_the_trace_of_a_small_run(small_cell):
    """A traced run on the CPU: the profiler's own file, the program's
    records, the harness's loop.  Each reader finds its number, and the
    numbers hold together."""
    cell = small_cell("signoff.batch")
    args = argparse.Namespace(workload="signoff.batch", seed=2**35 + 17, seconds=0.3,
                              trace=1)
    out = bench.run(args, cell=cell, device_check=fake_tpu, compile_cache=False)
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(got), got
    assert 0 < got["plan_ms"] <= got["lower_ms"]
    assert got["dispatch_ms"] > 0
    assert 0 < got["row_cycle_step_efficiency"] <= 100
    assert got["row_cycle_block_steps"] >= 1
