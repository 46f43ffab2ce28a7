"""Share of the HBM roofline the wire kernels reach: the bytes each launch
has to move (every operand read and result written once, from its array
types, ``bench/harness/counts.py::hlo_bytes``) over the HBM peak, divided
by the launches' summed device time. The wire kernels do a few integer
operations per byte, so bandwidth bounds them; the masked uplink's
in-kernel mask hashing is the one place that could make one compute
bound, and then this share reads low."""


def read(ctx):
    ops, peaks = ctx["wire_ops"], ctx["peaks"]
    total_s = sum(sec for _b, sec in ops)
    if not ops or not peaks or total_s <= 0:
        return None
    total_bytes = sum(b for b, _s in ops)
    return 100.0 * total_bytes / peaks["hbm_bytes_per_s"] / total_s
