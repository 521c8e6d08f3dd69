"""The STCO benchmark harness on the CPU: sizes, the trace reduction, the
roofline count, lookup by name, and the shape of a run's result line."""

import argparse
import io
import json
import math
import shutil
import subprocess
import sys
from contextlib import redirect_stdout
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks.stco import bench, harness, peaks, reference, roofline, spaces, trace
from conftest import fake_tpu

ROWS = {  # cell: (design rows, kernel rows) of one study
    "signoff.batch": (299_008, 598_016),
}


@pytest.mark.parametrize("name", sorted(ROWS))
def test_cell_builds_its_declared_rows(name):
    cell = harness.load_cell(name)
    spec = spaces.study_spec(cell.config, cell.mix, seed=2**33 + 1, index=0)
    design, kernel = ROWS[name]
    assert len(spaces.design_space(spec)) == design
    assert reference.study_len(spec) == design
    assert design * (2 if spec["replica"] else 1) == kernel
    assert len(reference.base_rows(spec)) == 73


def _synthetic_run(devices=(0,)):
    ms = 1_000_000
    spans = [("stco.window", 0, 100 * ms),
             ("stco.study", 0, 50 * ms), ("stco.plan", 0, 10 * ms),
             ("stco.study", 50 * ms, 100 * ms), ("stco.plan", 50 * ms, 56 * ms)]
    ops = {0: [("row_cycle_fused.1", 10 * ms, 30 * ms),
               ("fusion.3", 25 * ms, 35 * ms),           # overlaps: counted once
               ("fusion.2", 40 * ms, 42 * ms),
               ("row_cycle_fused.1", 60 * ms, 90 * ms),
               ("fusion.2", 95 * ms, 96 * ms)]}
    modules = {0: [("jit_score_from_events(7)", 40 * ms, 42 * ms),
                   ("jit_score_from_events(7)", 95 * ms, 96 * ms)]}
    if 1 in devices:
        ops[1] = [("row_cycle_fused.1", 10 * ms, 20 * ms)]
    tr = trace.from_events(ops, spans, modules)
    loop = SimpleNamespace(work=[(4e9, 1e6), (4e9, 1e6)])
    return SimpleNamespace(trace=tr, devices=list(devices), loop=loop,
                           peak=peaks.peaks("TPU v5 lite"))


def _read(name, run):
    return harness.metric_reader(name)(run)


def test_trace_reduction_on_a_synthetic_trace():
    run = _synthetic_run()
    assert run.trace.window_s == pytest.approx(0.1)
    assert trace.busy_s(run.trace, 0) == pytest.approx(0.058)   # 10-35, 40-42, 60-90, 95-96 ms
    assert _read("device_idle", run) == pytest.approx(100 * (1 - 0.058 / 0.1))
    assert _read("kernel_launches", run) == 1.0
    assert _read("row_cycle_ms", run) == pytest.approx(25.0)    # (20 + 30) / 2 studies
    assert _read("score_ms", run) == pytest.approx(1.5)
    assert _read("lower_ms", run) == pytest.approx(8.0)
    least = 4e9 / 197e12
    assert _read("row_cycle_roofline", run) == pytest.approx(
        100 * (least / 0.020 + least / 0.030) / 2)
    gaps = trace.idle_gaps(run.trace)
    assert gaps[0][1] == pytest.approx(0.018) and gaps[0][0] == "stco.plan"


def test_trace_reduction_takes_the_slowest_chip_and_means_idle_over_chips():
    run = _synthetic_run(devices=(0, 1))
    assert _read("row_cycle_ms", run) == pytest.approx(25.0)
    assert _read("kernel_launches", run) == pytest.approx(0.75)
    assert _read("device_idle", run) == pytest.approx(100 * (1 - (0.058 + 0.010) / 2 / 0.1))


def test_op_names_are_cut_from_the_hlo_text():
    assert trace.op_name("%row_cycle_fused.1 = (f32[2048,4]) custom-call(f32[2048,6] %copy)"
                         ) == "row_cycle_fused.1"


def test_readers_find_nothing_in_an_empty_trace():
    run = _synthetic_run()
    run.trace = trace.from_events({}, [("stco.window", 0, 10)])
    for name in ("kernel_launches", "row_cycle_ms", "row_cycle_roofline", "score_ms",
                 "device_idle", "lower_ms"):
        assert _read(name, run) is None


def test_roofline_hand_count_on_four_rows():
    cfg = harness.load_cell("signoff.batch").config
    dt = cfg["row_cycle"]["dt_ns"]
    # rows: fired at step 100, 150; a timed-out ACT; a timed-out tail
    t_fire = np.asarray([100 * dt, 150 * dt, np.nan, 80 * dt], np.float32)
    t_sense = t_fire + 1.0
    over = cfg["techs"]["si"]["t_overhead_ns"]
    trc = t_sense + over + np.asarray([300, 250, 400, np.nan]) * dt
    batch = SimpleNamespace(t_fire_ns=t_fire, t_sense_ns=t_sense, trc_ns=trc,
                            tech_idx=np.zeros(4, np.int32), tech_names=("si",))
    n_act = cfg["row_cycle"]["act_steps"]
    tail_cap = cfg["row_cycle"]["restore_steps"] + cfg["row_cycle"]["pre_steps"]
    act = 100 + 150 + n_act + 80
    # NaN t_fire makes t_sense and tRC NaN too: its tail is the full window
    tail = 300 + 250 + tail_cap + tail_cap
    ops, nbytes = roofline.study_work(cfg, {"replica": True}, batch)
    assert ops == (2 * act + tail) * roofline.OPS_PER_STEP
    assert nbytes == 8 * (4 * (4 * 6 + 5 + 6) + 16)
    ops1, _ = roofline.study_work(cfg, {"replica": False}, batch)
    assert ops1 == (act + tail) * roofline.OPS_PER_STEP
    t, which = roofline.bound(ops, nbytes, peaks.peaks("TPU v5 lite"))
    assert which == "flops" and t == pytest.approx(ops / 197e12)


def test_peaks_refuse_an_unknown_chip():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    here = tmp_path / "benchmarks" / "stco"
    for sub in ("configs", "traffic", "limits", "metrics", "loops"):
        (here / sub).mkdir(parents=True)
    (here / "configs" / "tiny.json").write_text(json.dumps({"name": "tiny", "grid": []}))
    (here / "traffic" / "trickle.json").write_text(json.dumps({"loop": "drip"}))
    (here / "loops" / "drip.py").write_text(
        "class Loop:\n    def __init__(self, cell, seed, traced):\n        self.seed = seed\n")
    (here / "limits" / "tiny.trickle.json").write_text(json.dumps({"input_gap": 0}))
    (here / "metrics" / "answer_count.py").write_text(
        "def read(run):\n    return 42.0 if run.loop else None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "benchmarks/stco/configs/tiny.json"}],
        "workloads": [{"name": "tiny.trickle", "config": "tiny", "traffic": "trickle",
                       "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "other", "unit": "s", "workloads": ["elsewhere"]}],
        "per_layer": [{"name": "answer_count", "unit": "answers",
                       "workloads": ["tiny.trickle"]}]}))
    cell = harness.load_cell("tiny.trickle", root=tmp_path, here=here)
    assert cell.config["name"] == "tiny" and cell.mix["loop"] == "drip"
    assert harness.loop_class("drip", here=here)(cell, 5, False).seed == 5
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["answer_count"]
    assert harness.metric_reader("answer_count", here=here)(SimpleNamespace(loop=1)) == 42.0
    with pytest.raises(harness.BenchError):
        harness.load_cell("absent", root=tmp_path, here=here)


def test_every_metric_and_workload_has_its_files():
    bench_json = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for m in bench_json["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
    for w in bench_json["workloads"]:
        cell = harness.load_cell(w["name"])
        assert hasattr(harness.loop_class(cell.mix["loop"]), "window")
        assert set(cell.limits) == set(bench.compare.NUMBERS)


def _main_lines(monkeypatch, small_cell, name, trace_flag=0):
    cell = small_cell(name)
    monkeypatch.setattr(harness, "load_cell", lambda _name: cell)
    monkeypatch.setattr(harness, "require_devices", fake_tpu)
    real_run = bench.run
    monkeypatch.setattr(bench, "run", lambda args: real_run(
        args, device_check=fake_tpu, compile_cache=False))
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench.main(["--workload", name, "--seed", str(2**35 + 9),
                         "--seconds", "0.5", "--trace", str(trace_flag)])
    return rc, out.getvalue().strip().splitlines()


@pytest.mark.parametrize("name", ["signoff.batch"])
def test_result_line(monkeypatch, small_cell, name):
    rc, lines = _main_lines(monkeypatch, small_cell, name)
    assert rc == 0
    last = json.loads(lines[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    assert "setup_s" in last["metrics"]
    assert all(m["value"] > 0 for m in last["metrics"].values())
    assert set(last["checks"]) == set(bench.compare.NUMBERS)
    assert all(c["value"] <= c["limit"] for c in last["checks"].values())
    window = [ln for ln in lines if ln.startswith("window: ")]
    assert len(window) == 1 and "compilations" in json.loads(window[0][8:])


def test_no_accelerator_no_result(capsys):
    rc = bench.main(["--workload", "signoff.batch", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc == 2
    assert "no TPU" in captured.err
    for line in captured.out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_a_checkout_without_the_program_gives_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "benchmarks" / "stco",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run([sys.executable, "benchmarks/stco/bench.py", "--workload",
                           "signoff.batch", "--seed", "1", "--seconds", "1"],
                          cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "no system under test" in proc.stderr
    assert proc.stdout.strip() == ""


def test_checks_print_infinity_as_null(monkeypatch, capsys):
    out = {"correct": False, "attempted": 1, "failed": 0, "metrics": {}, "device": {},
           "checks": {"event_step_gap": {"value": math.inf, "limit": 2}}}
    monkeypatch.setattr(bench, "run", lambda args: out)
    assert bench.main(["--workload", "x", "--seed", "1", "--seconds", "1"]) == 0
    assert json.loads(capsys.readouterr().out.splitlines()[-1])[
        "checks"]["event_step_gap"]["value"] is None


def test_setup_clock_counts_from_process_start():
    assert 0 < harness.process_age_s() < 24 * 3600


def test_args_namespace_drives_a_run(monkeypatch, small_cell):
    cell = small_cell("signoff.batch")
    args = argparse.Namespace(workload="signoff.batch", seed=2**36 + 1, seconds=0.2,
                              trace=0)
    out = bench.run(args, cell=cell, device_check=fake_tpu, compile_cache=False)
    assert out["correct"] is True
    assert out["metrics"]["design_rows_per_s"]["value"] > 0
