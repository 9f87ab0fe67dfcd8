"""Operation and byte counts of the QuClassi work, against hand-worked
numbers, and their independence from the bank mode."""
import pytest

from bench import harness, workcount
from bench.kinds import train

CFG7 = harness.config("quclassi-7q-3l")
#: the same ansatz at register width 8 (17 qubits, 44 angles, 16 data
#: angles from the raw pixels), whose banks spill 6 states per lane
CFG17 = dict(CFG7, n_qubits=17, n_theta=44, n_data=16, bank_kernel="spill", spill_states=6)


def test_circuit_ops_by_hand():
    # 7q/3l: m = 3, 8 amplitudes; 6 data + 14 trainable gates at 14 ops per
    # amplitude, then an 8-amplitude inner product at 8: 8 * (280 + 8)
    assert workcount.circuit_ops(7, 14, 6) == 2304
    # 17q/3l: m = 8, 256 amplitudes; 16 + 44 gates: 256 * (14 * 60 + 8)
    assert workcount.circuit_ops(17, 44, 16) == 217088


def test_bank_by_hand():
    # 16 images x 9 patches = 144 lanes, 1 + 2 * 14 = 29 groups
    w = workcount.bank_work(CFG7, 144, 29)
    assert w["circuits"] == 4176
    assert w["ops"] == 4176 * 2304
    # angles in: 144 lanes x 20 f32; fidelities out: 4176 f32
    assert w["bytes"] == 144 * 20 * 4 + 4176 * 4
    # a spilled 17q bank also writes and reads 6 states of 2 x 256 f32 per lane
    s = workcount.bank_work(CFG17, 576, 89, spill_states=6)
    assert s["bytes"] == 576 * 60 * 4 + 576 * 89 * 4 + 2 * 6 * 576 * 2 * 256 * 4


def test_roofline_bound():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    rows = workcount.rows_work(CFG7, 1000)
    assert workcount.bound(rows, peak) == "bytes"
    assert workcount.roofline_s(rows, peak) == pytest.approx(1000 * 84 / 819e9)


@pytest.mark.parametrize("cfg", [CFG7, CFG17], ids=["7q", "17q"])
def test_bank_mode_leaves_the_count(cfg):
    tr = dict(harness.traffic("train4"), bank_mode="implicit")
    implicit = train.window_work(cfg, tr, steps=3)
    materialized = train.window_work(cfg, dict(tr, bank_mode="materialized"), steps=3)
    assert implicit == materialized
    (banks, bank), = implicit.values()
    assert banks == 3 * cfg["n_classes"]
    # a materialized bank is lanes x groups row circuits: the same operations
    lanes, groups = 16 * 9, 2 * cfg["n_theta"] + 1
    assert bank["ops"] == workcount.rows_work(cfg, lanes * groups)["ops"]
