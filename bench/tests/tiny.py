"""Tiny configurations and cells for the CPU tests: the same structure as
the committed ones, at sizes the Pallas interpreter runs in seconds."""
import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def _load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def tiny_xlstm():
    cfg = _load("configs", "xlstm-350m-1p")
    cfg["arch"].update(d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
                       vocab=128)
    wl = _load("workloads", "xlstm-1p.plain.n4")
    wl.update(seq_len=16)
    return wl, cfg


def tiny_phi4():
    cfg = _load("configs", "phi4-mini-1l")
    cfg["arch"].update(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                       d_ff=128, vocab=128)
    wl = _load("workloads", "phi4-1l.secagg16-tree2-drop.n4")
    wl.update(seq_len=16, rounds_per_call=3, check_rounds=3)
    return wl, cfg


def tiny_mesh():
    """The four-chip cell at the tiny xLSTM's sizes, on four host devices."""
    cfg = tiny_xlstm()[1]
    wl = _load("workloads", "xlstm-1p.xsilo.4chip")
    wl.update(seq_len=16)
    return wl, cfg


def with_changes(pair, **wl_kw):
    wl, cfg = copy.deepcopy(pair)
    wl.update(wl_kw)
    return wl, cfg
