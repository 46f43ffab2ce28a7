"""Device time of flattening a round: operations whose ``op_name`` lies
under the program's ``fed/flatten`` or ``fed/unflatten`` scope
(``core/flat.py::flatten_tree`` and ``unflatten_tree``, every caller),
mean over the chips, per round of the traced calls. Nothing to read where
no operation ran under either scope."""

SCOPES = ("fed/flatten", "fed/unflatten")


def read(ctx):
    by = ctx["device_s_by_scope"]
    if not any(s in by for s in SCOPES) or not ctx["rounds"]:
        return None
    sec = sum(by.get(s, 0.0) for s in SCOPES)
    return 1e3 * sec / ctx["reduction"].chips / ctx["rounds"]
