"""Finding the benchmark's parts by name.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own under ``bench/``:

* ``bench/configs/<config>.json``   — sizes, source, cuts, reference name;
* ``bench/reference/<reference>.py`` — the plain model (``param_spec``,
  ``loss``) and its ``train_flops_per_token(arch, seq_len)``;
* ``bench/workloads/<cell>.json``   — traffic: wire, workers, token shapes,
  rounds per call, which driver runs it;
* ``bench/drivers/<driver>.py``     — a ``Cell`` class that drives the
  program for one cell;
* ``bench/metrics/<metric>.py``     — a reader with ``read(ctx)``;
* ``bench/peaks.json``              — the chip's peaks, by ``device_kind``;
* ``BENCHMARK.json`` (repo root)    — which metrics each cell reports.

A later cell, configuration or metric is a new file and a new entry in
``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A name that no file answers to, or a file that breaks the rules."""


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"no such file: {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def workload(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    w = _load_json(bench_dir / "workloads" / f"{name}.json")
    if w.get("name") != name:
        raise SpecError(f"workload file {name}.json names {w.get('name')!r}")
    return w


def config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    c = _load_json(bench_dir / "configs" / f"{name}.json")
    if c.get("name") != name:
        raise SpecError(f"config file {name}.json names {c.get('name')!r}")
    return c


def module(kind: str, name: str, bench_dir: Path = BENCH_DIR):
    """The module ``bench/<kind>/<name>.py``, loaded from its file (once
    per process and file)."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no such file: {kind}/{name}.py under {bench_dir}")
    mod_name = "bench_module_" + "".join(
        c if c.isalnum() else "_" for c in str(path.resolve()))
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[mod_name]
            raise
    return sys.modules[mod_name]


def _attribute(kind: str, name: str, attr: str, bench_dir: Path):
    mod = module(kind, name, bench_dir)
    if not hasattr(mod, attr):
        raise SpecError(f"{kind}/{name}.py has no {attr!r}")
    return getattr(mod, attr)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return _attribute("metrics", name, "read", bench_dir)


def driver(name: str, bench_dir: Path = BENCH_DIR):
    """The ``Cell`` class of ``bench/drivers/<name>.py``: built as
    ``Cell(workload, config, seed)``, with ``setup()``, ``window(seconds)``,
    ``call()``, ``hlo_texts()`` (of the compiled programs the calls run),
    ``program_counters()`` (counts the program keeps, for ``ctx``),
    ``release()``, ``check(limits)``, ``rounds`` and ``tokens_per_call``
    a call."""
    return _attribute("drivers", name, "Cell", bench_dir)


def reference(name: str, bench_dir: Path = BENCH_DIR):
    """The plain reference ``bench/reference/<name>.py``."""
    return module("reference", name, bench_dir)


def flops_counter(name: str, bench_dir: Path = BENCH_DIR):
    """``train_flops_per_token(arch, seq_len)`` of a configuration's
    reference."""
    return _attribute("reference", name, "train_flops_per_token", bench_dir)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The peak row of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = _load_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """(end-to-end metrics, per-layer metrics) that ``cell`` reports:
    each entry of ``BENCHMARK.json`` without a ``workloads`` key, or whose
    ``workloads`` lists the cell."""
    if not any(w["name"] == cell for w in bench["workloads"]):
        raise SpecError(f"cell {cell!r} is not in BENCHMARK.json")
    pick = lambda ms: [m for m in ms
                       if "workloads" not in m or cell in m["workloads"]]
    return pick(bench["end_to_end"]), pick(bench["per_layer"])
