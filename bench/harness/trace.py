"""From a profiler trace to the numbers the per-layer readers need.

The trace is JAX's own ``.xplane.pb``, read with ``ProfileData``. Device
planes are ``/device:TPU:<i>``; the operations of a device are the events
of its ``XLA Ops`` line, each named by its HLO instruction text
(``%name = type opcode(operands...)``). The window is the span from the
first to the last of the benchmark's own ``bench/call`` annotations (host
clock, on the same timeline). Per device:

* busy: the union of its operation intervals inside the window (control
  flow such as a ``while`` is an operation that spans its body's);
* wire kernels: the Mosaic kernels (custom calls to ``tpu_custom_call``)
  launched under a ``wire/<kind>/r<rows>n<N>/<backend>`` scope of
  ``telemetry/profile.py``. A trace event carries neither its scope nor
  its kernel's name, so both come from the metadata ``op_name`` of the
  same instruction in the compiled program's HLO text
  (``kernel_scopes``); a Mosaic kernel outside a wire scope is not a wire
  kernel.

Idle gaps inside the window are attributed to the innermost host span
that covers their midpoint: the benchmark's ``bench/...`` spans and the
program's own ``fed/...`` ``TraceAnnotation`` spans (``fed/scan`` and its
children ``fed/scan/prepare``, ``/state``, ``/audit``, ``/schedules``,
``/compile``, ``/dispatch``, ``/finish``, ``/wait``, ``/ledger``,
``/trace``), on the device's clock.

Every operation is also given the device scope it ran under: the
``jax.named_scope`` names (``fed/train/optimizer``, ``fed/flatten``,
``fed/unflatten``, ``wire/...``) in its instruction's metadata
``op_name`` in the compiled programs' HLO text. A trace event names only
its instruction, so the scope is looked up there, and only for events that
ran inside one of those programs (the device's ``XLA Modules`` line):
instruction names repeat across programs.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field

from bench.harness.counts import hlo_bytes

MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
WIRE_SCOPE_RE = re.compile(r"(?:^|/)wire/([\w.-]+)/r\d+n\d+/[\w-]+/"
                           r"(?:.*?jit\(([\w.-]+)\))?")
HLO_KERNEL_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+) = .*'
                           + re.escape(MOSAIC_MARK) + r'.*?op_name="([^"]*)"')
CONTROL_RE = re.compile(r"^%?[\w.-]+ = .*? (while|conditional|call)\(")
HLO_NAME_RE = re.compile(r"^%?([A-Za-z_-]+?)[.\d]*( = |$)")
OP_NAME_RE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+) = .*?op_name="([^"]*)"')
MODULE_RE = re.compile(r"^HloModule ([\w.-]+)")
CALL_SPAN = "bench/call"
SPAN_PREFIXES = ("bench/", "fed/")     # host spans that label idle gaps
# Device scopes that name a layer, in the order they are tested.
SCOPES = ("fed/train/optimizer/", "fed/flatten/", "fed/unflatten/")


@dataclass
class Op:
    name: str             # HLO instruction text
    start: float          # seconds on the trace clock
    dur: float
    scope: str = ""       # the compiled HLO's op_name, for Mosaic kernels

    @property
    def is_wire(self) -> bool:
        return MOSAIC_MARK in self.name and bool(WIRE_SCOPE_RE.search(
            self.scope))

    @property
    def is_control(self) -> bool:
        return bool(CONTROL_RE.match(self.name))


@dataclass
class Reduction:
    window_s: float
    busy_s: list          # per device
    ops: list             # per device, [Op] inside the window
    gaps: list = field(default_factory=list)   # [(label, seconds)]
    host_spans: list = field(default_factory=list)
    # [(op_name, Op)], all devices, containers left out: op_name "" where
    # the instruction carries none, None where the operation ran in
    # another program than the ones handed over (an eager call)
    scoped: list = field(default_factory=list)

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def load(path_or_dir: str):
    from jax.profiler import ProfileData
    if path_or_dir.endswith(".xplane.pb"):
        path = path_or_dir
    else:
        found = sorted(glob.glob(f"{path_or_dir}/**/*.xplane.pb",
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path_or_dir}")
        path = found[-1]
    return ProfileData.from_file(path)


def kernel_scopes(hlo_texts) -> dict:
    """``{instruction name: op_name}`` of every Mosaic kernel in the
    compiled programs' HLO texts."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = HLO_KERNEL_RE.match(line)
            if m:
                out[m.group(1)] = m.group(2)
    return out


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


def _instruction(text: str) -> str:
    return text.partition(" = ")[0].strip().lstrip("%")


def _scoped(ops: list, runs: list, modules: set, names: dict) -> list:
    """``[(op_name, Op)]`` of one device's operations, containers left
    out. ``runs`` is the device's sorted ``[(start_s, end_s, module)]``;
    a device whose trace has none is taken as running only ``modules``."""
    out = []
    for op in ops:
        if op.is_control:
            continue
        if runs:
            i = bisect.bisect_right(runs, (op.start, float("inf"), "")) - 1
            if i < 0 or not (runs[i][0] <= op.start <= runs[i][1]
                             and runs[i][2] in modules):
                out.append((None, op))
                continue
        out.append((names.get(_instruction(op.name), ""), op))
    return out


def reduce_profile(pd, hlo_texts=()) -> Reduction:
    """Reduce a ``ProfileData`` (or any object with the same shape);
    ``hlo_texts`` are the ``as_text()`` of the compiled programs that ran
    in the window."""
    hlo_texts = list(hlo_texts)
    scopes = kernel_scopes(hlo_texts)
    modules, names = op_names(hlo_texts)
    spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append((plane.name, lines["XLA Ops"],
                                lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
    calls = [s for s in spans if s[0] == CALL_SPAN]
    if not calls or not devices:
        raise ValueError(f"trace holds {len(calls)} {CALL_SPAN} spans and "
                         f"{len(devices)} device op lines")
    w0 = min(s for _, s, _ in calls)
    w1 = max(e for _, _, e in calls)
    busy, ops_all, gaps, scoped = [], [], [], []
    for _name, line, mods in sorted(devices, key=lambda d: d[0]):
        ops, iv = [], []
        for ev in line.events:
            s = ev.start_ns * 1e-9
            e = s + ev.duration_ns * 1e-9
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            ops.append(Op(ev.name, s, e - s,
                          scopes.get(_instruction(ev.name), "")
                          if MOSAIC_MARK in ev.name else ""))
            iv.append((s, e))
        busy.append(union_length(iv))
        ops_all.append(ops)
        gaps.extend(_gaps(iv, w0, w1, spans))
        runs = sorted((ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9,
                       ev.name.split("(")[0])
                      for ev in (mods.events if mods else ()))
        scoped.extend(_scoped(ops, runs, modules, names))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s=w1 - w0, busy_s=busy, ops=ops_all,
                     gaps=gaps, host_spans=spans, scoped=scoped)


def _gaps(iv, w0, w1, spans):
    out, cur = [], w0
    for s, e in sorted(iv):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        out.append((cur, w1))
    labelled = []
    for s, e in out:
        mid = (s + e) / 2
        cover = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        label = (min(cover, key=lambda sp: sp[2] - sp[1])[0]
                 if cover else "outside bench spans")
        labelled.append((label, e - s))
    return labelled


def op_label(op: Op) -> str:
    """A stable group name: a wire kernel by its scope's kind and its
    kernel function, else the HLO instruction name without its instance
    number."""
    if op.is_wire:
        kind, fn = WIRE_SCOPE_RE.search(op.scope).groups()
        return f"wire/{kind} {fn or 'kernel'}"
    if op.scope:
        fn = re.findall(r"jit\(([\w.-]+)\)", op.scope)
        return f"mosaic {fn[-1] if fn else op.scope[-60:]}"
    m = HLO_NAME_RE.match(op.name)
    return m.group(1) if m else op.name[:60]


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The device operations that took most time (mean over devices; the
    bodies' operations, not the ``while`` that holds them) and the longest
    idle gaps, by what the host was doing."""
    by = {}
    for ops in red.ops:
        for op in ops:
            if op.is_control:
                continue
            lab = op_label(op)
            by[lab] = by.get(lab, 0.0) + op.dur / red.chips
    dev = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    gap_by = {}
    for lab, sec in red.gaps:
        gap_by.setdefault(lab, []).append(sec)
    idle = sorted(((f"{lab} (longest of {len(v)})", max(v))
                   for lab, v in gap_by.items()),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in dev],
            "idle_gaps": [[k, v] for k, v in idle]}


def wire_ops(red: Reduction) -> list:
    """``[(bytes, seconds)]`` of every wire launch, all devices."""
    return [(hlo_bytes(op.name), op.dur)
            for ops in red.ops for op in ops if op.is_wire]


def idle_by_span(red: Reduction) -> dict:
    """``{label: seconds}`` of the window's idle gaps by the innermost
    span at their midpoint, mean over the devices."""
    out: dict = {}
    for label, sec in red.gaps:
        out[label] = out.get(label, 0.0) + sec / red.chips
    return out


def scope_of(op_name) -> str:
    """The program scope an operation's ``op_name`` lies in."""
    if op_name is None:
        return "(other programs)"
    for mark in SCOPES:
        if mark in op_name:
            return mark.rstrip("/")
    m = re.search(r"(?:^|/)(wire/[\w.-]+)/", op_name)
    return m.group(1) if m else "(no scope)"


def device_s_by_scope(red: Reduction) -> dict:
    """``{scope_of(op_name): seconds}`` of the window's operations, summed
    over the devices."""
    out: dict = {}
    for name, op in red.scoped:
        scope = scope_of(name)
        out[scope] = out.get(scope, 0.0) + op.dur
    return out
