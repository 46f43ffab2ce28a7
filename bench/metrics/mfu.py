"""Model FLOPs utilization of the traced window: the training tokens of
every worker in the traced calls, times the model FLOPs of one training
token (forward and backward, from the configuration's shapes), over the
window's length, the chips in use and their bf16 peak. The models hold
float32 parameters at the default matmul precision, which the chip runs as
bfloat16 passes, so bf16 is the peak that bounds them."""


def read(ctx):
    red, peaks = ctx["reduction"], ctx["peaks"]
    if not ctx["tokens"] or red.window_s <= 0 or not peaks:
        return None
    flops = ctx["flops_per_token"] * ctx["tokens"]
    return 100.0 * flops / (red.window_s * red.chips
                            * peaks["bf16_flops_per_s"])
