"""Device time of the wire kernels (the Mosaic kernels of the round:
uplink, tree partial sums, mask repair, master) per round, averaged over
the chips in use."""


def read(ctx):
    ops = ctx["wire_ops"]
    if not ops or not ctx["rounds"]:
        return None
    chips = ctx["reduction"].chips
    return 1e3 * sum(sec for _b, sec in ops) / chips / ctx["rounds"]
