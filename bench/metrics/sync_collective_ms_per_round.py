"""Device time of the collective operations a round (all-gather,
all-reduce, reduce-scatter, collective-permute, all-to-all, with their
asynchronous ``-start`` and ``-done`` halves), mean over the chips, per
round of the traced calls: the exchange between chips of the cross-chip
sync. An operation is known by the opcode of its HLO instruction text,
which names every trace event. Nothing to read where none ran."""
import re

COLLECTIVE_RE = re.compile(
    r"^%?[\w.-]+ = .*? (all-gather|all-reduce|reduce-scatter|"
    r"collective-permute|all-to-all)(-start|-done)?\(")


def read(ctx):
    red = ctx["reduction"]
    sec = sum(op.dur for ops in red.ops for op in ops
              if COLLECTIVE_RE.match(op.name))
    if sec <= 0 or not ctx["rounds"]:
        return None
    return 1e3 * sec / red.chips / ctx["rounds"]
