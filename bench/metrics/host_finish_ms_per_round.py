"""Device idle time a round while the simulator driver finishes a call
outside the fetch that waits on the program (``fed/scan/wait``): the idle
gaps whose innermost host span is ``fed/scan/finish``, ``fed/scan/ledger``
or ``fed/scan/trace``, mean over the chips, per round of the traced calls.
Nothing to read where the program wrote no ``fed/scan`` span."""

FINISH = ("fed/scan/finish", "fed/scan/ledger", "fed/scan/trace")


def read(ctx):
    red = ctx["reduction"]
    if not ctx["rounds"] or not any(n == "fed/scan"
                                    for n, _, _ in red.host_spans):
        return None
    idle = ctx["idle_by_span"]
    return 1e3 * sum(idle.get(lab, 0.0) for lab in FINISH) / ctx["rounds"]
