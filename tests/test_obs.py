"""`repro.obs`: spans, counters, compile attribution, the bounded buffer,
and the spans and counters the sweep records."""

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import dse, transient
from repro.core.space import DesignSpace
from repro.kernels import ops


@pytest.fixture()
def fresh(monkeypatch):
    """An empty record buffer for the test (the module's is process-wide)."""
    buf = collections.deque(maxlen=obs.BUFFER_RECORDS)
    monkeypatch.setattr(obs, "_records", buf)
    return buf


def named(name):
    return [r for r in obs.records() if r.name == name]


def test_spans_nest_with_parent_root_and_self_time(fresh):
    with obs.span("outer"):
        time.sleep(0.002)
        with obs.span("inner.a"):
            time.sleep(0.003)
        with obs.span("inner.b"), obs.span("leaf"):
            time.sleep(0.001)
    with obs.span("second"):
        pass
    recs = {r.name: r for r in obs.records()}
    outer = recs["outer"]
    assert outer.parent is None and outer.root == outer.id
    assert recs["inner.a"].parent == outer.id and recs["inner.b"].parent == outer.id
    assert recs["leaf"].parent == recs["inner.b"].id
    assert {recs[n].root for n in ("inner.a", "inner.b", "leaf")} == {outer.id}
    assert recs["second"].root == recs["second"].id != outer.id
    # children lie inside their parent, on one clock
    for child in ("inner.a", "inner.b"):
        assert outer.start_ns <= recs[child].start_ns <= recs[child].end_ns <= outer.end_ns
    children = recs["inner.a"].duration_ns + recs["inner.b"].duration_ns
    assert outer.self_ns == outer.duration_ns - children
    assert outer.self_ns >= 2_000_000
    assert recs["leaf"].self_ns == recs["leaf"].duration_ns
    # finished records come oldest-exit first: children before parents
    assert [r.name for r in obs.records()] == ["inner.a", "leaf", "inner.b", "outer", "second"]


def test_a_span_closes_on_an_exception(fresh):
    with pytest.raises(RuntimeError), obs.span("fails"):
        raise RuntimeError("boom")
    with obs.span("after"):
        pass
    (after,) = named("after")
    assert after.parent is None                  # the failed span left the stack


def test_counters_sum_device_scalars_when_read(fresh):
    three, six = jnp.int32(3), jnp.sum(jnp.arange(4, dtype=jnp.int32))
    with obs.span("work"):
        obs.count("steps", three)
        obs.count("steps", six)
        obs.count("steps", 5)
        obs.count("launches", 1)
        with obs.span("child"):
            obs.count("launches", 2)             # the innermost span only
    obs.count("dropped", 1)                      # outside any span: dropped
    (work,) = named("work")
    assert work.counters == {"steps": 14, "launches": 1}
    assert type(work.counters["steps"]) is int
    assert named("child")[0].counters == {"launches": 2}
    s = obs.summary()["spans"]
    assert s["work"]["count"] == 1 and s["work"]["counters"] == {"steps": 14, "launches": 1}
    assert s["work"]["self_ms"] <= s["work"]["total_ms"]


def test_compiles_are_counted_on_the_innermost_span(fresh):
    before = obs.summary()["compile"]
    fresh_fn = jax.jit(lambda x: x * 3.0 + 1.0)       # a new program: compiles
    with obs.span("outer"):
        with obs.span("compiling"):
            fresh_fn(jnp.ones(7)).block_until_ready()
        with obs.span("warm"):
            fresh_fn(jnp.ones(7)).block_until_ready()
    after = obs.summary()["compile"]
    (inner,) = named("compiling")
    got = inner.counters
    assert got.get("compiles", 0) + got.get("cache_loads", 0) >= 1
    if got.get("compiles"):
        assert got["compile_s"] > 0
    for quiet in ("outer", "warm"):
        assert not {"compiles", "cache_loads"} & set(named(quiet)[0].counters)
    assert (after["programs"] - before["programs"]
            + after["cache_loads"] - before["cache_loads"]) >= 1


def test_the_buffer_keeps_the_last_records(fresh):
    extra = 10
    for i in range(obs.BUFFER_RECORDS + extra):
        with obs.span(f"s{i}"):
            pass
    recs = obs.records()
    assert len(recs) == obs.BUFFER_RECORDS
    assert recs[0].name == f"s{extra}" and recs[-1].name == f"s{obs.BUFFER_RECORDS + extra - 1}"


def test_the_sweep_records_its_layers(fresh):
    space = DesignSpace.paper_targets().with_replica().with_mc(samples=8, key=0)
    dse.sweep(space, b_chunk=64)
    recs = {r.name: r for r in obs.records()}
    root = recs["dse.sweep"]
    assert root.parent is None
    assert recs["dse.plan"].parent == root.id
    for child in ("dse.plan.lower", "dse.plan.parasitics", "dse.plan.operands"):
        assert recs[child].parent == recs["dse.plan"].id
    for step in ("engine.dispatch", "dse.finalize"):
        assert recs[step].parent == root.id
    assert all(r.root == root.id for r in recs.values())


@pytest.mark.parametrize("backend", ["auto", "pallas"])
def test_engine_dispatch_counts_launches_rows_and_block_steps(fresh, monkeypatch,
                                                              backend):
    """Counters of one chunked dispatch against what the kernel seam saw:
    every launch, the rows given and padded, and the sum of the launches'
    own block step counts, on the oracle (one block per launch) and on the
    kernel (64 rows padded to one 128-lane block)."""
    plan = dse.plan_sweep(DesignSpace.paper_grid())
    b = int(plan.operands.c.shape[0])
    seen = []
    real = ops.row_cycle_fused

    def recording(*args, **kw):
        out = real(*args, **kw)
        seen.append((args[0].shape[0], np.asarray(out.block_steps)))
        return out
    monkeypatch.setattr(ops, "row_cycle_fused", recording)
    transient.simulate_row_cycle_lowered(plan.operands, backend, b_chunk=64)
    (rec,) = named("engine.dispatch")
    got = rec.counters
    assert got["launches"] == len(seen) == -(-b // 64) > 1
    assert got["rows"] == b
    block = ops.row_cycle_block_rows(64, backend)
    assert all(len(s) == -(-n // block) for n, s in seen)
    assert got["rows_padded"] == sum(-(-n // block) * block for n, _ in seen)
    assert got["block_steps"] == sum(int(s.sum()) for _, s in seen)
    caps = transient.N_ACT_STEPS + transient.N_RESTORE_STEPS + transient.N_PRE_STEPS
    assert all(((s > 0) & (s <= caps)).all() for _, s in seen)


def test_a_seam_without_a_count_leaves_block_steps_out(fresh, monkeypatch):
    real = ops.row_cycle_fused
    monkeypatch.setattr(ops, "row_cycle_fused", lambda *a, **k: tuple(real(*a, **k)))
    plan = dse.plan_sweep(DesignSpace.paper_targets())
    transient.row_cycle_events(plan.operands)
    (rec,) = named("engine.dispatch")
    assert rec.counters["launches"] == 1 and "block_steps" not in rec.counters


def _abstract_operands(b):
    plan = dse.plan_sweep(DesignSpace.paper_grid())
    return [jax.ShapeDtypeStruct((b,) + x.shape[1:], x.dtype) for x in plan.operands[:6]]


def test_engine_dispatch_never_waits_for_the_device(fresh):
    """Nothing inside `engine.dispatch` needs a value from the device: the
    whole chunk loop traces with abstract operands (a host read of any
    array in it would raise), and a planned sweep's dispatch runs with
    device-to-host transfers disallowed (enforced where the backend
    enforces the guard; the CPU backend does not)."""
    dispatch = lambda *x: transient._row_cycle_fused_chunked(x, "auto", 64)
    jaxpr = jax.make_jaxpr(dispatch)(*_abstract_operands(5 * 64 + 3))
    assert str(jaxpr).count("row_cycle_fused") >= 6
    fresh.clear()                                # records holding tracers
    plan = dse.plan_sweep(DesignSpace.paper_targets().with_mc(samples=40, key=0))
    with jax.transfer_guard_device_to_host("disallow"):
        res = transient.simulate_row_cycle_lowered(plan.operands, b_chunk=64)
    dse.finalize_sweep(plan, res)
    assert named("engine.dispatch")[0].counters["launches"] > 1


def test_engine_dispatch_host_programs_do_not_grow_with_chunks(fresh):
    """The dispatch issues the same number of non-kernel programs at 5
    and at 20 chunks (one pad, one split, one join of the outputs and
    step counts, two cuts of the padded rows), and counts one split on a
    multi-chunk batch and none on an unpadded single chunk."""
    dispatch = lambda *x: transient._row_cycle_fused_chunked(x, "auto", 64)

    def other_programs(n_chunks):
        jaxpr = jax.make_jaxpr(dispatch)(*_abstract_operands(n_chunks * 64 - 3))
        kernel = [e for e in jaxpr.jaxpr.eqns if e.params.get("name") == "row_cycle_fused"]
        assert len(kernel) == n_chunks
        return len(jaxpr.jaxpr.eqns) - len(kernel)
    assert other_programs(5) == other_programs(20) <= 5
    fresh.clear()                                # records holding tracers
    plan = dse.plan_sweep(DesignSpace.paper_targets().with_mc(samples=40, key=0))
    transient.simulate_row_cycle_lowered(plan.operands, b_chunk=64)
    (multi,) = named("engine.dispatch")
    assert multi.counters["launches"] > 1 and multi.counters["chunk_splits"] == 1
    fresh.clear()
    transient._row_cycle_fused_chunked(
        [x[:64] for x in plan.operands[:6]], "auto", 64)
    (single,) = named("engine.dispatch")
    assert single.counters["launches"] == 1
    assert single.counters.get("chunk_splits", 0) == 0


def test_a_host_read_inside_the_dispatch_is_caught(fresh, monkeypatch):
    """The check above has teeth: a seam that reads an event on the host
    fails the same trace."""
    real = ops.row_cycle_fused

    def syncing(*args, **kw):
        out = real(*args, **kw)
        int(out[0][0, 0])
        return out
    monkeypatch.setattr(ops, "row_cycle_fused", syncing)
    dispatch = lambda *x: transient._row_cycle_fused_chunked(x, "auto", 64)
    with pytest.raises(jax.errors.ConcretizationTypeError):
        jax.make_jaxpr(dispatch)(*_abstract_operands(64))
