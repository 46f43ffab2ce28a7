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
        assert callable(spec.driver(wl["driver"]))
        assert callable(spec.flops_counter(
            spec.config(wl["config"])["reference"]))
        e2e, per_layer = spec.cell_metrics(bench, cell["name"])
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert per_layer
        for m in per_layer:
            assert callable(spec.metric_reader(m["name"]))


THROWAWAY_REFERENCE = """
from bench.reference import xlstm


def param_spec(arch):
    return xlstm.param_spec(arch)


def loss(params, tokens, arch):
    return xlstm.loss(params, tokens, arch)


def train_flops_per_token(arch, seq_len):
    return 7.0 * seq_len
"""
THROWAWAY_DRIVER = """
class Cell:
    def __init__(self, wl, cfg, seed):
        self.wl, self.cfg, self.seed = wl, cfg, seed
"""
# a metric that reads a device scope and a host span from ctx
THROWAWAY_METRIC = """
def read(ctx):
    by = ctx["device_s_by_scope"]
    idle = ctx["idle_by_span"]
    if "fed/flatten" not in by:
        return None
    return by["fed/flatten"] + idle.get("fed/scan/audit", 0.0)
"""


def test_new_files_are_found_without_edits(tmp_path):
    """A later cell is a new workload file; a later configuration a new
    config file and its reference; a later driver a new driver file; a
    later metric, one that reads a device scope and a host span too, a new
    reader. No file that is already there changes."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*")
              if p.is_file()}
    wl = json.loads((bench_dir / "workloads/xlstm-1p.plain.n4.json")
                    .read_text())
    wl.update(name="xlstm-1p.plain.n8", config="xlstm-throwaway",
              driver="throwaway")
    (bench_dir / "workloads/xlstm-1p.plain.n8.json").write_text(
        json.dumps(wl))
    cfg = json.loads((bench_dir / "configs/xlstm-350m-1p.json").read_text())
    cfg.update(name="xlstm-throwaway", reference="throwaway")
    (bench_dir / "configs/xlstm-throwaway.json").write_text(json.dumps(cfg))
    (bench_dir / "reference/throwaway.py").write_text(THROWAWAY_REFERENCE)
    (bench_dir / "drivers/throwaway.py").write_text(THROWAWAY_DRIVER)
    (bench_dir / "metrics/flatten_plus_audit.py").write_text(
        THROWAWAY_METRIC)

    got_wl = spec.workload("xlstm-1p.plain.n8", bench_dir)
    got_cfg = spec.config(got_wl["config"], bench_dir)
    cell = spec.driver(got_wl["driver"], bench_dir)(got_wl, got_cfg, 5)
    assert (cell.wl, cell.cfg, cell.seed) == (got_wl, got_cfg, 5)
    ref = spec.reference(got_cfg["reference"], bench_dir)
    assert set(ref.param_spec(got_cfg["arch"])) == {"embed", "norm_f",
                                                    "units", "lm_head"}
    assert spec.flops_counter(got_cfg["reference"], bench_dir)(
        got_cfg["arch"], 3) == 21.0
    read = spec.metric_reader("flatten_plus_audit", bench_dir)
    assert read({"device_s_by_scope": {"fed/flatten": 2.0},
                 "idle_by_span": {"fed/scan/audit": 0.5}}) == 2.5
    assert read({"device_s_by_scope": {}, "idle_by_span": {}}) is None
    assert all(p.read_bytes() == b for p, b in before.items())


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.workload("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.config("no-such-config")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.driver("no_such_driver")
    with pytest.raises(spec.SpecError):
        spec.reference("no_such_reference")
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
    from bench.drivers.simulator import SimCell
    from bench.tests import tiny
    wl, cfg = tiny.with_changes(tiny.tiny_xlstm(), rounds_per_call=1,
                                check_rounds=1, setup_calls=2)
    cell = SimCell(wl, cfg, seed=2 ** 34 + 3)
    cell.setup()
    read = spec.metric_reader("wire_bytes_per_round")
    assert read(cell.program_counters()) is None
    res = cell.call()
    want = res.bytes_per_round[0] + res.recovery_bytes_per_round[0]
    assert cell.program_counters() == {"wire_bytes_per_round": want}
    assert want > 0
    assert read(cell.program_counters()) == want
    cell.release()
