"""FLOP and byte counts against hand counts at small shapes, and the
committed configurations' counts to the FLOP."""
import json
from pathlib import Path

import pytest

from bench.harness import counts, spec

BENCH = Path(__file__).resolve().parents[1]


def _flops(reference, arch, seq_len):
    return spec.flops_counter(reference)(arch, seq_len)


def test_decoder_flops_by_hand():
    arch = {"d_model": 8, "vocab": 10, "n_layers": 1,
            "pattern": [["attn", "mlp"]], "n_heads": 2, "n_kv_heads": 1,
            "head_dim": 4, "d_ff": 16}
    s = 3
    # q 8x8, k 8x4, v 8x4, o 8x8: 2*(64+32+32+64) = 384
    # scores+values: 2*2*2 heads*4 dh*(3+1)/2 = 64; SwiGLU 3*2*8*16 = 768
    # head 2*8*10 = 160
    fwd = 384 + 64 + 768 + 160
    assert _flops("decoder", arch, s) == 3 * fwd


def test_xlstm_flops_by_hand():
    arch = {"d_model": 4, "vocab": 6, "n_layers": 2, "n_heads": 2,
            "lstm_proj_factor": 2.0,
            "pattern": [["mlstm", "none"], ["slstm", "none"]]}
    di, h, dh = 8, 2, 4
    mlstm = (2 * 4 * 2 * di + 3 * 2 * di * di + 2 * di * 2 * h
             + 2 * di * 4 + h * (5 * dh * dh + 5 * dh))
    slstm = 2 * 4 * 16 + 2 * 4 * 16 + 2 * 4 * 4
    assert _flops("xlstm", arch, 16) == 3 * (
        mlstm + slstm + 2 * 4 * 6)


def test_published_sizes_are_plausible():
    """The xLSTM head alone is 2*1024*50304 FLOPs a token forward."""
    x = json.loads((BENCH / "configs/xlstm-350m-1p.json").read_text())
    f = _flops(x["reference"], x["arch"], 2048)
    assert 3 * 2 * 1024 * 50304 < f < 3 * 2 * 131_359_752 * 1.2


@pytest.mark.parametrize("config,seq_len,want", [
    ("xlstm-350m-1p", 2048, 494_794_752),
    ("phi4-mini-1l", 256, 1_069_664_256)])
def test_committed_counts_do_not_move(config, seq_len, want):
    """The counts every cell's ``mfu`` has used since the first benchmark,
    now read from the configuration's reference, to the FLOP."""
    cfg = spec.config(config)
    assert _flops(cfg["reference"], cfg["arch"], seq_len) == want


def test_a_reference_without_a_count_is_refused(tmp_path):
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "plain.py").write_text(
        "def loss(p, t, arch):\n    return 0.0\n")
    with pytest.raises(spec.SpecError, match="reference/plain.py"):
        spec.flops_counter("plain", tmp_path)


def test_hlo_bytes_by_hand():
    text = ('%tpu.54 = u16[4,8,512]{2,1,0:T(8,128)(2,1)} custom-call('
            'f32[4,8,512]{2,1,0:T(8,128)} %a, f32[8,512]{1,0:T(8,128)} %b, '
            'u32[16]{0:T(128)S(1)} %k, pred[2]{0} %m, bf16[3,2]{1,0} %h), '
            'custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={f32[4,8,512]{2,1,0}}')
    want = (4 * 8 * 512 * 2 + 4 * 8 * 512 * 4 + 8 * 512 * 4 + 16 * 4 + 2
            + 3 * 2 * 2)
    assert counts.hlo_bytes(text) == want
    assert counts.hlo_bytes("%x = f32[] custom-call()") == 4
