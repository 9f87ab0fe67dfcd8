"""``correct`` separates sound runs from wrong ones.

At a size a CPU test can hold (5 qubits, 1 layer, tiny batches, the Pallas
kernels interpreted), each test drives a whole run through ``run_cell``
with the look for a chip skipped: a sound run reads under each of the
cell's limits; the control (the reference one precision lower, in the
program's place) and every fault the cell can have, planted in the timed
path, read over one of them.
"""
from types import SimpleNamespace

import jax
import pytest

from bench import control, harness
from bench import run as bench_run

SMALL = {"n_qubits": 5, "n_layers": 1, "n_theta": 4, "n_data": 4}
TRAIN = {"tenants": 2, "batch": 2, "steps_per_call": 2, "test_images": 2, "pool_chunks": 2}
SERVE = {"rate_per_s": 5, "warmup_s": 1}
SEED = 2**33 + 17


#: the cells and the end-to-end metrics each reports: q7l3.serve is held
#: out of BENCHMARK.json until its rate is measured on the chip
CELLS = {
    "q7l3.train4": ({"config": "quclassi-7q-3l", "traffic": "train4", "chips": 1},
                    ["circuits_per_s", "setup_s"]),
    "q7l3.serve": ({"config": "quclassi-7q-3l", "traffic": "serve", "chips": 1},
                   ["circuits_per_s", "p50_ms", "p95_ms", "setup_s"]),
}


def _cell(name):
    cell = dict(CELLS[name][0], name=name)
    cfg = dict(harness.config(cell["config"]), **SMALL)
    small = TRAIN if name.endswith("train4") else SERVE
    tr = dict(harness.traffic(cell["traffic"]), **small)
    return cell, cfg, tr


def _run(name, monkeypatch):
    cell, cfg, tr = _cell(name)
    monkeypatch.setattr(harness, "peak", lambda kind: {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    args = SimpleNamespace(seed=SEED, seconds=1.0, trace=0)
    line, checks = bench_run.run_cell(
        cell, cfg, tr, harness.limits(name), harness.kind(tr["kind"]),
        jax.devices()[:1], args, [{"name": m, "unit": "x"} for m in CELLS[name][1]],
    )
    assert set(line["metrics"]) == set(CELLS[name][1])
    return line, checks


def _alter_first_group(fn):
    """Kernel wrapper whose base-group fidelities come out 0.01 too high."""

    def altered(*a, **kw):
        out = fn(*a, **kw)
        if isinstance(out, tuple):
            return tuple(o.at[0].add(0.01) for o in out)
        return out.at[0].add(0.01)

    return altered


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
    elif fault == "altered":
        for name in ("vqc_fidelity_shiftgroups", "vqc_fidelity_shiftgroups_multibank",
                     "vqc_fidelity"):
            monkeypatch.setattr(ops, name, _alter_first_group(getattr(ops, name)))


@pytest.mark.parametrize("name", ["q7l3.train4", "q7l3.serve"])
def test_sound_run_is_correct(name, monkeypatch):
    line, checks = _run(name, monkeypatch)
    assert line["correct"], checks
    assert line["failed"] == 0


@pytest.mark.parametrize(
    "name,fault",
    [
        ("q7l3.train4", "unchanged"),
        ("q7l3.train4", "half_batch"),
        ("q7l3.train4", "altered"),
        ("q7l3.serve", "altered"),
    ],
)
def test_fault_is_not_correct(name, fault, monkeypatch):
    _plant(fault, monkeypatch)
    line, checks = _run(name, monkeypatch)
    assert not line["correct"], checks


@pytest.mark.parametrize("name", ["q7l3.train4", "q7l3.serve"])
def test_control_reads_over_a_limit(name):
    cell, cfg, tr = _cell(name)
    lim = harness.limits(name)
    for kind, nums in control.readings(cfg, tr, SEED, seconds=1.0):
        assert any(v > lim[k] for k, v in nums.items()), (kind, nums)
