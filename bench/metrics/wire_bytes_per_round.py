"""Bytes a round puts on the wire, from the simulator's own byte ledger
(`SimResult.bytes_per_round + recovery_bytes_per_round`: the data plane of
Eq. (8) plus dropout-recovery dealing and reconstruction, derived from the
round's device counts and cross-checked by `telemetry/trace.py`), averaged
over the traced calls' rounds. A count, exact on any device."""


def read(ctx):
    return ctx.get("wire_bytes_per_round")
