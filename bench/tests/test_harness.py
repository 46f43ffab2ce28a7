"""The harness finds configurations, cells and metrics by name, refuses
what it does not know, and never runs without a chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import spec

ROOT = Path(__file__).resolve().parents[2]


def test_every_cell_and_metric_is_found_by_name():
    bench = spec.benchmark()
    for cfg in bench["configs"]:
        c = spec.config(cfg["name"])
        assert (ROOT / cfg["file"]).is_file()
        assert set(cfg["reduced"]) == set(c["reduced"])
    for cell in bench["workloads"]:
        wl = spec.workload(cell["name"])
        assert wl["config"] == cell["config"]
        assert wl["chips"] == cell["chips"]
        e2e, per_layer = spec.cell_metrics(bench, cell["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert per_layer
        for m in per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_new_files_are_found_without_edits(tmp_path):
    """A later cell is a new workload file; a later metric a new reader."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    wl = json.loads((bench_dir / "workloads/xlstm-1p.plain.n4.json")
                    .read_text())
    wl["name"] = "xlstm-1p.plain.n8"
    (bench_dir / "workloads/xlstm-1p.plain.n8.json").write_text(
        json.dumps(wl))
    (bench_dir / "metrics/answer.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    assert spec.workload("xlstm-1p.plain.n8", bench_dir)["name"] == \
        "xlstm-1p.plain.n8"
    assert spec.metric_reader("answer", bench_dir)({}) == 42.0


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.workload("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.config("no-such-config")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.cell_metrics(spec.benchmark(), "no-such-cell")


def test_unknown_device_kind_is_refused():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99")


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "xlstm-1p.plain.n4",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_no_result():
    r = _run(ROOT)
    assert r.returncode == 2
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_bench_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and bench/ (no program) exits
    with an error and prints no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_wire_bytes_come_from_the_window_calls():
    """The per-layer byte reader takes the simulator's own ledger of the
    calls that ran after set-up, nothing before."""
    from bench.harness.simcell import SimCell
    from bench.tests import tiny
    wl, cfg = tiny.with_changes(tiny.tiny_xlstm(), rounds_per_call=1,
                                check_rounds=1, setup_calls=2)
    cell = SimCell(wl, cfg, seed=2 ** 34 + 3)
    cell.setup()
    assert cell.wire_bytes_per_round() is None
    res = cell.call()
    want = res.bytes_per_round[0] + res.recovery_bytes_per_round[0]
    assert cell.wire_bytes_per_round() == want > 0
    read = spec.metric_reader("wire_bytes_per_round")
    assert read({"wire_bytes_per_round": want}) == want
    cell.release()
