"""Device idle time a round while the simulator driver prepares a call:
the idle gaps whose innermost host span is ``fed/scan/prepare`` or one of
its children (``state``, ``audit``, ``schedules``, ``compile``), mean over
the chips, per round of the traced calls. Idle time, not the spans'
length: the chip waits on the host only where it has nothing to run.
Nothing to read where the program wrote no ``fed/scan`` span."""

PREPARE = ("fed/scan/prepare", "fed/scan/state", "fed/scan/audit",
           "fed/scan/schedules", "fed/scan/compile")


def read(ctx):
    red = ctx["reduction"]
    if not ctx["rounds"] or not any(n == "fed/scan"
                                    for n, _, _ in red.host_spans):
        return None
    idle = ctx["idle_by_span"]
    return 1e3 * sum(idle.get(lab, 0.0) for lab in PREPARE) / ctx["rounds"]
