"""Operations and bytes that the work needs, from shapes alone.

The shared terms of a model's FLOP count, as forward multiply-adds counted
as 2 FLOPs each, per token. Each configuration's reference
(``bench/reference/<name>.py``) adds up its own layers from these in its
``train_flops_per_token(arch, seq_len)``: model FLOPs of one training
token, forward and backward (3x the forward), recomputation not counted,
the output head counted whether tied or not. Attention counts its causal
half of the score matrix; the recurrent cells count the work of their
recurrences.

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
BACKWARD = 3.0        # forward plus backward, in units of the forward


def mlstm_flops(d: int, heads: int, proj_factor: float) -> float:
    """One mLSTM block: up-projection, q/k/v, gates, down-projection, and
    per head the recurrence ``C <- f C + i v k^T``, ``n <- f n + i k``,
    ``C q`` and ``n.q``."""
    di = int(proj_factor * d) // heads * heads
    dh = di // heads
    return (2 * d * 2 * di + 3 * 2 * di * di + 2 * di * 2 * heads
            + 2 * di * d + heads * (3 * dh * dh + 2 * dh * dh + 5 * dh))


def slstm_flops(d: int) -> float:
    """One sLSTM block: input gates, recurrent gates, output projection."""
    return 2 * d * 4 * d + 2 * d * 4 * d + 2 * d * d


def attention_flops(d: int, q_heads: int, kv_heads: int, head_dim: int,
                    seq_len: int) -> float:
    """Grouped-query causal attention: projections, then scores and values
    over the mean number of keys a query sees."""
    proj = 2 * d * (q_heads + 2 * kv_heads) * head_dim
    proj += 2 * q_heads * head_dim * d
    return proj + 2 * 2 * q_heads * head_dim * (seq_len + 1) / 2


def swiglu_flops(d: int, d_ff: int) -> float:
    return 3 * 2 * d * d_ff


def head_flops(d: int, vocab: int) -> float:
    return 2 * d * vocab


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
