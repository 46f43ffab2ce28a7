"""The program's own labels in a profiler trace: where the host and the
device spend a call of ``FedSimulator.run_fedpc_scan``.

Two kinds of label, both written by the program:

* host spans: ``TraceAnnotation`` events named ``fed/...`` on the host
  plane (``fed/scan`` and its children ``fed/scan/prepare``, ``/state``,
  ``/audit``, ``/schedules``, ``/compile``, ``/dispatch``, ``/finish``,
  ``/wait``, ``/ledger``, ``/trace``), on the device's clock. An idle gap
  takes the name of the innermost span at its midpoint, the rule of
  ``trace._gaps``;
* device scopes: ``jax.named_scope`` names (``fed/train/optimizer``,
  ``fed/flatten``, ``fed/unflatten``, ``wire/...``) in each instruction's
  metadata ``op_name`` of the compiled program's HLO text. A trace event
  names only its instruction, so its scope is looked up there, as
  ``trace.kernel_scopes`` does for the wire kernels, and only for events
  that ran inside one of those programs (its ``XLA Modules`` event):
  instruction names repeat across programs.

Each reading works on a ``trace.Reduction`` (the window of the benchmark's
``bench/call`` spans) and is averaged per round of the window. These are
not yet per-layer metrics of ``BENCHMARK.json``: ``bench/run.py`` drops
the profile once ``trace.reduce_profile`` has read it, so
``bench/spans.py`` runs a cell to read them (PERF.md §7).
"""
from __future__ import annotations

import bisect
import re

from bench.harness import trace

FED = "fed/"
# The driver's host steps, by the idle gaps they hold: the chip waits on
# ``prepare`` and on ``finish`` outside ``wait`` (the fetch that waits on
# the program).
PREPARE = ("fed/scan/prepare", "fed/scan/state", "fed/scan/audit",
           "fed/scan/schedules", "fed/scan/compile")
FINISH = ("fed/scan/finish", "fed/scan/ledger", "fed/scan/trace")
OPTIMIZER = ("fed/train/optimizer/",)
FLATTEN = ("fed/flatten/", "fed/unflatten/")
OP_NAME_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+) = .*?op_name="([^"]*)"')
MODULE_RE = re.compile(r"^HloModule ([\w.-]+)")


def op_names(hlo_texts) -> tuple[set, dict]:
    """(module names, ``{instruction: op_name}``) of compiled programs'
    HLO texts; every instruction with metadata."""
    modules, names = set(), {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = OP_NAME_RE.match(line)
            if m:
                names[m.group(1)] = m.group(2)
            elif MODULE_RE.match(line):
                modules.add(MODULE_RE.match(line).group(1))
    return modules, names


def host_spans(pd) -> list:
    """``[(name, start_s, end_s)]`` of the host events named ``fed/...``."""
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(FED)]


def _module_runs(pd) -> list:
    """Per device, in ``reduce_profile``'s order: the sorted
    ``[(start_s, end_s, module name)]`` of its ``XLA Modules`` line (the
    name without the ``(program id)`` suffix)."""
    out = []
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = lines.get("XLA Modules")
        out.append(sorted(
            (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name.split("(")[0]) for ev in (mods.events if mods else ())))
    return out


def scoped_ops(red: trace.Reduction, pd, modules: set, names: dict) -> list:
    """``[(op_name, Op)]`` of every operation in the window (all devices;
    not the ``while`` and other containers). ``op_name`` is ``""`` where
    the instruction carries none, and ``None`` where the operation ran in
    another program than ``modules`` (an eager call); a device whose trace
    has no ``XLA Modules`` line is taken as running only ``modules``."""
    runs = _module_runs(pd)
    out = []
    for d, ops in enumerate(red.ops):
        dev_runs = runs[d] if d < len(runs) else []
        for op in ops:
            if op.is_control:
                continue
            if dev_runs:
                i = bisect.bisect_right(dev_runs,
                                        (op.start, float("inf"), "")) - 1
                if i < 0 or not (dev_runs[i][0] <= op.start <= dev_runs[i][1]
                                 and dev_runs[i][2] in modules):
                    out.append((None, op))
                    continue
            out.append((names.get(trace._instruction(op.name), ""), op))
    return out


def idle_gaps(red: trace.Reduction, spans: list) -> list:
    """``[(label, seconds)]`` of every device's idle gaps in the window,
    each named by the innermost ``fed/`` or ``bench/`` span at its
    midpoint; ``red.gaps`` with the program's spans among the labels."""
    calls = [s for s in red.host_spans if s[0] == trace.CALL_SPAN]
    w0, w1 = min(s for _, s, _ in calls), max(e for _, _, e in calls)
    spans = list(spans) + red.host_spans
    out = []
    for ops in red.ops:
        iv = [(op.start, op.start + op.dur) for op in ops]
        out.extend(trace._gaps(iv, w0, w1, spans))
    return out


def idle_by_span(red: trace.Reduction, gaps: list) -> dict:
    """``{label: seconds}`` of :func:`idle_gaps`, mean over the devices."""
    out: dict = {}
    for label, sec in gaps:
        out[label] = out.get(label, 0.0) + sec / red.chips
    return out


def readings(red: trace.Reduction, gaps: list, ops: list,
             rounds: int) -> dict:
    """The four per-round numbers, and the shares of the window's device
    operation time that no scope names and that ran outside the round
    programs (eager calls, counted as unnamed too).

    ``gaps`` from :func:`idle_gaps`; ``ops`` from :func:`scoped_ops`."""
    idle = idle_by_span(red, gaps)
    host = lambda labels: sum(idle.get(lab, 0.0) for lab in labels)
    device = lambda marks: sum(op.dur for name, op in ops
                               if name and any(m in name for m in marks))
    total = sum(op.dur for _, op in ops)
    unscoped = total - device((FED, "wire/"))
    eager = sum(op.dur for name, op in ops if name is None)
    per_round = 1e3 / rounds
    share = lambda sec: 100.0 * sec / total if total else None
    return {
        "host_prep_ms_per_round": host(PREPARE) * per_round,
        "host_finish_ms_per_round": host(FINISH) * per_round,
        "optimizer_ms_per_round": device(OPTIMIZER) / red.chips * per_round,
        "flatten_ms_per_round": device(FLATTEN) / red.chips * per_round,
        "unscoped_device_share": share(unscoped),
        "other_programs_device_share": share(eager),
    }
