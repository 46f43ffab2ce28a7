"""Profiler labels: named device scopes and host spans.

Every kernel wrapper in ``repro.kernels.ops`` (and the tree/masked entry
points it fronts) launches inside a :func:`kernel_scope` named after the
tuner's table key — ``wire/<kind>/r<rows>n<N>/<backend>`` — so a real-TPU
``jax.profiler`` capture attributes device time to the same identities the
autotuner plans. The round body adds two more device scopes, as plain
``jax.named_scope``: ``fed/train/optimizer`` around each local step's
optimizer update (``Worker.scan_train``), and ``fed/flatten`` /
``fed/unflatten`` around the flat-buffer conversions of ``core/flat.py``.
A named scope annotates metadata only: it adds no jaxpr equations (the
round program still counts exactly two pallas launches and zero host
syncs, pinned by tests/test_telemetry.py), and it reaches the compiled
program's HLO text as each instruction's ``op_name``, which is where a
trace reduction looks it up (a device trace event names only its
instruction).

Host work is labelled with ``jax.profiler.TraceAnnotation`` directly:
a span on the profiler's host plane, on the same clock as the device's
``XLA Ops`` line, so a device idle gap can be attributed to what the host
was doing during it. ``FedSimulator.run_fedpc_scan`` marks its steps with
them (``fed/scan`` and its children). With no profiler session active a
span costs about a microsecond.
"""
from __future__ import annotations

import jax


def scope_name(kind: str, rows: int, n: int = 1,
               interpret: bool | None = None) -> str:
    """The profiler label of one launch site, keyed like the tune table."""
    from repro.kernels import tune
    return f"wire/{kind}/r{int(rows)}n{max(1, int(n))}/" \
           f"{tune.backend_tag(interpret)}"


def kernel_scope(kind: str, rows: int, n: int = 1,
                 interpret: bool | None = None):
    """``jax.named_scope`` over a kernel launch, named by its tuner key."""
    return jax.named_scope(scope_name(kind, rows, n, interpret))

