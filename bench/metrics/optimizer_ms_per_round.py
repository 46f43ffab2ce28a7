"""Device time of the local optimizers' updates a round: operations whose
``op_name`` lies under the program's ``fed/train/optimizer`` scope
(``Worker.scan_train``: ``opt.update`` and ``apply_updates``), mean over
the chips, per round of the traced calls. Nothing to read where no
operation ran under that scope."""

SCOPE = "fed/train/optimizer"


def read(ctx):
    sec = ctx["device_s_by_scope"].get(SCOPE)
    if not sec or not ctx["rounds"]:
        return None
    return 1e3 * sec / ctx["reduction"].chips / ctx["rounds"]
