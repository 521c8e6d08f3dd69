"""Small cells of the STCO benchmark for CPU tests: the committed cell's
files, cut to a size a test run holds (few MC samples, a short sample)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def fake_tpu(chips):
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


@pytest.fixture()
def small_cell(monkeypatch):
    """`small_cell(name)`: the named cell, shrunk.  The engine's chunk is
    cut to 128 rows where `repro.core.dse` is first imported after this
    (its `sweep` binds the chunk as a default), so a study may run as one
    chunk or several: a test must hold for both."""
    from benchmarks.stco import harness
    from repro.core import transient

    monkeypatch.setattr(transient, "DEFAULT_B_CHUNK", 128)

    def make(name):
        cell = harness.load_cell(name)
        cell.mix.update(mc_samples=4, sample_rows=96)
        return cell
    return make
