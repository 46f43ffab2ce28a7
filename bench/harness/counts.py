"""Operations and bytes that the work needs, from shapes alone.

``train_flops_per_token``: model FLOPs of one training token, forward and
backward (3x the forward's multiply-adds, counted as 2 FLOPs each),
recomputation not counted, the output head counted whether tied or not.
Attention counts its causal half of the score matrix; the recurrent cells
count the work of their recurrences.

``hlo_bytes``: the HBM bytes one launch of a kernel has to read and
write: every operand read once and every result written once, from the
array types in the kernel's HLO instruction text as the device trace
names it (a wire kernel streams each operand block through VMEM once).
"""
from __future__ import annotations

import re

TYPE_RE = re.compile(r"\b(pred|[subf](?:8|16|32|64)|bf16)\[([\d,]*)\]")
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}


def _forward_flops_per_token(arch: dict, seq_len: int) -> float:
    d, v = arch["d_model"], arch["vocab"]
    n_units = arch["n_layers"] // len(arch["pattern"])
    per_unit = 0.0
    for mixer, ffn in arch["pattern"]:
        if mixer == "mlstm":
            h = arch["n_heads"]
            di = int(arch["lstm_proj_factor"] * d) // h * h
            dh = di // h
            per_unit += 2 * d * 2 * di            # up-projection
            per_unit += 3 * 2 * di * di            # q, k, v
            per_unit += 2 * di * 2 * h             # input / forget gates
            per_unit += 2 * di * d                 # down-projection
            # recurrence per head: C <- f C + i v k^T, n <- f n + i k,
            # C q and n.q
            per_unit += h * (3 * dh * dh + 2 * dh * dh + 5 * dh)
        elif mixer == "slstm":
            per_unit += 2 * d * 4 * d              # input gates
            per_unit += 2 * d * 4 * d              # recurrent gates
            per_unit += 2 * d * d                  # output projection
        elif mixer == "attn":
            hq, hk, dh = arch["n_heads"], arch["n_kv_heads"], arch["head_dim"]
            per_unit += 2 * d * (hq + 2 * hk) * dh + 2 * hq * dh * d
            ctx = (seq_len + 1) / 2                # causal: mean keys seen
            per_unit += 2 * 2 * hq * dh * ctx      # scores and values
        else:
            raise ValueError(f"no FLOP count for mixer {mixer!r}")
        if ffn == "mlp":
            per_unit += 3 * 2 * d * arch["d_ff"]   # SwiGLU
        elif ffn != "none":
            raise ValueError(f"no FLOP count for ffn {ffn!r}")
    return n_units * per_unit + 2 * d * v           # output head


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    return 3.0 * _forward_flops_per_token(arch, seq_len)


def hlo_bytes(text: str) -> int:
    """Bytes of every array an HLO instruction writes and reads: its
    result type(s) and its operands' types, read from the instruction text
    ``%x = T custom-call(T a, T b, ...)``."""
    _, _, rest = text.partition(" = ")
    total = 0
    for dt, dims in TYPE_RE.findall(rest.split("custom_call_target")[0]):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total
