"""The chunk loop pads and cuts a batch in one program: each launch gets the
same chunk a plain per-chunk loop over the padded operands would give it,
so events, v_end and the kernel's step count are bitwise the same."""

import collections

import numpy as np
import pytest

from repro import obs
from repro.core import dse, transient
from repro.core.space import DesignSpace
from repro.kernels import ops

B_CHUNK = 64


def _mc_rows(n):
    """The first n rows of a sampled paper-grid plan (no replica pairs)."""
    plan = dse.plan_sweep(DesignSpace.paper_grid().with_mc(samples=8, key=1))
    return tuple(x[:n] for x in plan.operands[:6])


def _replica_mc():
    plan = dse.plan_sweep(DesignSpace.paper_targets().with_replica()
                          .with_mc(samples=40, key=0))
    return tuple(plan.operands[:6])


CASES = {
    "ragged_tail": lambda: _mc_rows(5 * B_CHUNK + 3),
    "exact": lambda: _mc_rows(4 * B_CHUNK),
    "replica_mc": _replica_mc,
}


def _plain_loop(operands, backend):
    """One kernel call per chunk, each chunk sliced from the padded batch."""
    b = operands[0].shape[0]
    padded = transient._pad_operands(operands, (-b) % B_CHUNK)
    outs = [ops.row_cycle_fused(*(x[lo:lo + B_CHUNK] for x in padded),
                                transient.DT_NS, transient.N_ACT_STEPS,
                                transient.N_RESTORE_STEPS, transient.N_PRE_STEPS,
                                backend=backend)
            for lo in range(0, padded[0].shape[0], B_CHUNK)]
    evt = np.concatenate([np.asarray(o[0]) for o in outs])[:b]
    v_end = np.concatenate([np.asarray(o[1]) for o in outs])[:b]
    steps = sum(int(np.asarray(o.block_steps).sum()) for o in outs)
    return evt, v_end, steps, len(outs)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_split_chunks_match_a_plain_loop_bitwise(monkeypatch, backend, case):
    monkeypatch.setattr(obs, "_records", collections.deque(maxlen=obs.BUFFER_RECORDS))
    operands = CASES[case]()
    b = operands[0].shape[0]
    assert b > B_CHUNK
    evt, v_end = transient._row_cycle_fused_chunked(operands, backend, B_CHUNK)
    want_evt, want_v, want_steps, n_chunks = _plain_loop(operands, backend)
    assert evt.shape == want_evt.shape == (b, 4)
    assert v_end.shape == want_v.shape
    np.testing.assert_array_equal(np.asarray(evt).view(np.uint32),
                                  want_evt.view(np.uint32))
    np.testing.assert_array_equal(np.asarray(v_end).view(np.uint32),
                                  want_v.view(np.uint32))
    (rec,) = [r for r in obs.records() if r.name == "engine.dispatch"]
    assert rec.counters["block_steps"] == want_steps
    assert rec.counters["launches"] == n_chunks == -(-b // B_CHUNK)
    assert rec.counters["chunk_splits"] == 1
