"""The two metrics of the spill path: the roofline reader finds the spill
kernel's launches by the names a compiled TPU program gives them, and the
tiles-per-bank reader reads the program's spill counters.

The compile is for a described ``v5e:2x2`` chip (no chip needed), of the
program's own jitted wrapper for one 17-qubit / 3-layer class bank at the
cell's 576 lanes; the topology is described inside a fixture, never at
import.
"""
import os
import re
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness, workcount


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_spill_kernel_names(one_chip, monkeypatch):
    from repro.core import circuits
    from repro.kernels import ops
    from repro.kernels import vqc_statevector as K

    # the process's backend is the CPU; the compile target is the TPU
    monkeypatch.setattr(K, "resolve_interpret", lambda interpret: False)
    spec = circuits.build_quclassi_circuit(17, 3)
    lanes = 576
    assert K.shift_execution_info(spec, lanes)["mode"] == "spill"
    groups = tuple(range(1 + 2 * spec.n_theta))
    t = jax.ShapeDtypeStruct((lanes, spec.n_theta), jnp.float32, sharding=one_chip)
    d = jax.ShapeDtypeStruct((lanes, spec.n_data), jnp.float32, sharding=one_chip)
    text = ops._shiftgroups_jit.lower(spec, t, d, False, groups).compile().as_text()
    names = [s.strip() for s in text.splitlines() if 'custom_call_target="tpu_custom_call"' in s]
    spill = harness.metric_reader("spill_roofline").PATTERN
    fused = harness.metric_reader("shiftbank_roofline").PATTERN
    assert len(names) == 2 and all(re.search(spill, n) for n in names), names
    assert not any(re.search(fused, n) for n in names), names


def _ctx(spill=None, op_seconds=None, banks=4):
    cfg = harness.config("quclassi-17q-3l")
    bank = workcount.bank_work(cfg, 576, 89, spill_states=cfg["spill_states"])
    result = {"kernel_work": {"spill": (banks, bank)}, "window_s": 10.0}
    if spill is not None:
        result["spill"] = spill
    trace = None if op_seconds is None else {"op_seconds": op_seconds}
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    return SimpleNamespace(result=result, trace=trace, peak=peak, chips=1), bank, peak


def test_spill_tiles_per_bank():
    read = harness.metric_reader("spill_tiles_per_bank.train_wide").read
    counters = {"spill_banks": 4, "spill_tiles": 20, "spill_boundary_bytes": 4 * 25165824}
    assert read(_ctx(counters)[0]) == 5.0
    # a program with no spill counters, or no spilled launch in the window
    assert read(_ctx()[0]) is None
    assert read(_ctx(dict(counters, spill_banks=0, spill_tiles=0))[0]) is None


def test_spill_roofline():
    read = harness.metric_reader("spill_roofline").read
    ops = {"%spill_forward.1 = (f32[1,1024])": 0.001, "%spill_tiles.1 = f32[88,1024]": 0.003,
           "%_shiftgroups_jit.2 = f32[29,256]": 5.0, "%fusion.3 = f32[2]": 7.0}
    ctx, bank, peak = _ctx(op_seconds=ops)
    want = 100.0 * 4 * workcount.roofline_s(bank, peak) / 0.004
    assert read(ctx) == pytest.approx(want)
    # no trace, or no spill launch in it: nothing to read
    assert read(_ctx()[0]) is None
    assert read(_ctx(op_seconds={"%_shiftgroups_jit.2 = f32[29,256]": 5.0})[0]) is None
