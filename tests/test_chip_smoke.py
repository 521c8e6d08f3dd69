"""`chip_smoke.py` phases on the CPU at a tiny size.

The phases run with `backend="pallas"` (interpret mode off the TPU) and
are checked against the oracle exactly as on the chip; `main()` itself
must refuse to run without a TPU.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

DT = 0.02
MC = 16


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_sweep_phase_matches_ref():
    out = chip_smoke.phase_sweep(samples=MC, backend="pallas")
    assert out["design_points"] == 73
    assert out["design_rows"] == 73 * MC
    assert out["kernel_rows"] == 2 * 73 * MC      # [replica, main] pairs
    assert out["vs_ref"]["rows"] == out["kernel_rows"]
    assert not out["pallas_compiled"]             # interpreter off the TPU


def test_sweep_phase_requires_compiled_kernel_on_tpu(monkeypatch):
    """On a TPU, an engine that is interpreted (or the oracle) fails."""
    monkeypatch.setattr(chip_smoke, "device_info",
                        lambda: {"platform": "tpu", "kind": "", "count": 1})
    with pytest.raises(chip_smoke.SmokeError, match="compiled Pallas"):
        chip_smoke.phase_sweep(samples=1, backend="pallas")


def test_anchor_phase():
    out = chip_smoke.phase_anchors(backend="pallas")
    assert out["selected"] == chip_smoke.SELECTED
    assert set(out["anchors"]) == {"si", "aos", "d1b"}


def test_service_phase():
    out = chip_smoke.phase_service(backend="pallas", mc_samples=MC)
    assert out["clients"] == 3
    assert out["memo_hits"] == 1


def test_sharded_phase_one_device():
    out = chip_smoke.phase_sharded(samples=2, n_dev=1, backend="pallas")
    assert out["devices"] == 1
    assert out["design_rows"] == 73 * 2


def events(rows):
    """(B, 4) events from (t_dev, dv, t_res, t_pre) tuples."""
    return np.asarray(rows, np.float32)


REF = events([(1.00, 0.200, 3.00, 2.00), (1.00, 0.100, np.nan, 2.00)])


def test_compare_events_accepts_one_step_fire_shift():
    # the second row fires one step later and samples a larger signal
    got = events([(1.00, 0.200, 3.00, 2.00), (1.02, 0.101, np.nan, 2.02)])
    out = chip_smoke.compare_events(got, REF, DT)
    assert out["rows_bit_identical"] == 1
    assert out["rows_fire_step_off"] == 1
    assert out["fire_step_off_max_dv_rel"] == pytest.approx(0.01, rel=1e-3)


@pytest.mark.parametrize("got,msg", [
    (events([(1.00, 0.200, np.nan, 2.00), (1.00, 0.100, np.nan, 2.00)]),
     "NaN pattern"),
    (events([(1.04, 0.200, 3.00, 2.00), (1.00, 0.100, np.nan, 2.00)]),
     "differ by 2 steps"),
    (events([(1.00, 0.201, 3.00, 2.00), (1.00, 0.100, np.nan, 2.00)]),
     "beyond rtol"),
    (events([(1.00, 0.200, 3.00, 2.00), (1.02, 0.099, np.nan, 2.00)]),
     "against the shift"),
])
def test_compare_events_rejects(got, msg):
    with pytest.raises(chip_smoke.SmokeError, match=msg):
        chip_smoke.compare_events(got, REF, DT)
