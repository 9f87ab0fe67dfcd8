"""``correct`` separates sound runs of ``q17l3.train`` from wrong ones.

At a size a CPU test can hold (9 qubits, 3 layers, one tenant, batches of
two, the Pallas kernels interpreted) and with the kernel's VMEM budget
lowered so that each class bank spills in depth tiles as the 17-qubit
banks do on the chip, each test drives a whole run through ``run_cell``:
a sound run reads under each limit; the control (the register reference
in bfloat16, in the program's place) and every fault planted in the timed
path read over one of them.
"""
from types import SimpleNamespace

import jax
import pytest

from bench import control, harness
from bench import run as bench_run

CELL = {"name": "q17l3.train", "config": "quclassi-17q-3l", "traffic": "train1", "chips": 1}
SMALL = {"n_qubits": 9, "n_layers": 3, "n_theta": 20, "n_data": 8, "n_ops": 34, "spill_states": 4}
TRAIN = {"batch": 2, "steps_per_call": 2, "test_images": 2, "pool_chunks": 2}
METRICS = ["circuits_per_s", "setup_s", "spill_tiles_per_bank.train_wide"]
SEED = 2**33 + 29


@pytest.fixture
def spill(monkeypatch):
    """Eight register states of checkpoint budget at one lane tile: a
    9-qubit bank (20 checkpoints) spills in three depth tiles."""
    from repro.kernels import vqc_statevector as K

    jax.clear_caches()
    monkeypatch.setattr(K, "VMEM_BUDGET_BYTES", (8 + 4) * 2 * 4 * 16 * K.LANES)
    yield
    jax.clear_caches()


def _cell():
    cfg = dict(harness.config(CELL["config"]), **SMALL)
    tr = dict(harness.traffic(CELL["traffic"]), **TRAIN)
    return cfg, tr


def _run(monkeypatch):
    cfg, tr = _cell()
    monkeypatch.setattr(harness, "peak", lambda kind: {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    args = SimpleNamespace(seed=SEED, seconds=1.0, trace=0)
    line, checks = bench_run.run_cell(
        CELL, cfg, tr, harness.limits(CELL["name"]), harness.kind(tr["kind"]),
        jax.devices()[:1], args, [{"name": m, "unit": "x"} for m in METRICS],
    )
    assert set(line["metrics"]) == set(METRICS)
    return line, checks


def _plant(fault, monkeypatch):
    from repro.core import quclassi
    from repro.kernels import ops
    from repro.optim import optimizers

    if fault == "unchanged":
        monkeypatch.setattr(optimizers, "apply_updates", lambda params, updates: params)
    elif fault == "half_batch":
        grad_shift = quclassi.grad_shift

        def half(cfg, params, images, labels, **kw):
            b = images.shape[0] // 2
            return grad_shift(cfg, params, images[:b], labels[:b], **kw)

        monkeypatch.setattr(quclassi, "grad_shift", half)
    elif fault == "altered":  # base-group fidelities 0.01 high
        shiftgroups = ops.vqc_fidelity_shiftgroups

        def altered(*a, **kw):
            return shiftgroups(*a, **kw).at[0].add(0.01)

        monkeypatch.setattr(ops, "vqc_fidelity_shiftgroups", altered)


def test_sound_run_is_correct(spill, monkeypatch, capsys):
    line, checks = _run(monkeypatch)
    assert line["correct"], checks
    assert line["failed"] == 0
    # one bank per launch: the plan's three tiles per bank
    assert line["metrics"]["spill_tiles_per_bank.train_wide"]["value"] == 3
    modes = [s for s in capsys.readouterr().err.splitlines() if s.startswith("modes: ")]
    assert len(modes) == 3
    for s in modes[:2]:
        assert "'spill': " in s and "mesh_spills=0" in s and "materialize" not in s
        assert "spill_banks=0" not in s


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_not_correct(fault, spill, monkeypatch):
    _plant(fault, monkeypatch)
    line, checks = _run(monkeypatch)
    assert not line["correct"], checks


def test_control_reads_over_a_limit():
    cfg, tr = _cell()
    lim = harness.limits(CELL["name"])
    readings = control.readings(cfg, tr, SEED, seconds=1.0)
    assert [kind for kind, _ in readings] == ["control", "half_batch", "unchanged"]
    for kind, nums in readings:
        assert any(v > lim[k] for k, v in nums.items()), (kind, nums)


def test_program_without_spill_counters_exits_at_once(monkeypatch):
    """The parent program's telemetry counts no spill launches: set-up
    exits before it builds a cluster."""
    from repro.serve.metrics import Telemetry

    monkeypatch.delattr(Telemetry, "on_spill_launch")
    cfg, tr = _cell()
    with pytest.raises(SystemExit, match="spill"):
        harness.kind(tr["kind"]).setup(cfg, tr, SEED, 1.0)
