"""CPU rehearsal of ``chip_smoke.py``: its phase functions run end to end
at ``cfg.reduced()`` widths in the Pallas interpreter, which proves the
script's control flow, entry points and reference checks without the chip.
The script itself still refuses to run anywhere but on a TPU."""
import importlib.util
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    assert capsys.readouterr().out == ""


def test_phase_a_rehearsal(smoke):
    out = smoke.phase_a(smoke.smoke_config().reduced(), rounds=2,
                        seq_len=16, on_chip=False)
    assert out["launches"] == 2
    assert out["uplink_bytes_exact"]
    assert out["master_worst_share_of_bound"] <= 1.0


def test_phase_b_rehearsal(smoke):
    out = smoke.phase_b(smoke.smoke_config().reduced(), rounds=2,
                        seq_len=16, on_chip=False)
    assert out["launches"] == 4          # uplink + 1 tree level + repair + root
    # fanout 2, threshold 2: the dead worker's sibling group degrades to an
    # exact-zero subtree; the flat cohort keeps 3 survivors and repairs
    assert out["tree"]["repaired"] == []
    assert out["flat"]["repaired"] == out["flat"]["dead"]
    assert len(out["flat"]["used"]) == smoke.N_WORKERS - 1
