"""The roofline readers find the program's kernels by the names a compiled
TPU program gives them.

Each case compiles the program's own jitted wrapper for a described
``v5e:2x2`` chip (no chip needed) and checks that the Mosaic call's
instruction, whose text the profiler's trace shows as the operation's
name, matches the reader's pattern.  The topology is described inside a fixture, never at import.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import harness


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def program(monkeypatch):
    from repro.core import circuits
    from repro.kernels import ops
    from repro.kernels import vqc_statevector as K

    # the process's backend is the CPU; the compile target is the TPU
    monkeypatch.setattr(K, "resolve_interpret", lambda interpret: False)
    return ops, circuits.build_quclassi_circuit(7, 3)


def _kernel_names(lowered) -> list[str]:
    """The Mosaic calls' instruction texts: the profiler's trace names each
    device operation by its HLO instruction's text."""
    text = lowered.compile().as_text()
    return [
        line.strip()
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def _angles(sharding, spec, rows):
    return (
        jax.ShapeDtypeStruct((rows, spec.n_theta), jnp.float32, sharding=sharding),
        jax.ShapeDtypeStruct((rows, spec.n_data), jnp.float32, sharding=sharding),
    )


def test_row_kernel_name(one_chip, program):
    ops, spec = program
    names = _kernel_names(ops.vqc_fidelity.lower(spec, *_angles(one_chip, spec, 128)))
    pattern = harness.metric_reader("rows_roofline").PATTERN
    assert names and all(re.search(pattern, n) for n in names), names
    shift = harness.metric_reader("shiftbank_roofline").PATTERN
    assert not any(re.search(shift, n) for n in names)


def test_shift_kernel_names(one_chip, program):
    ops, spec = program
    groups = tuple(range(1 + 2 * spec.n_theta))
    t, d = _angles(one_chip, spec, 144)
    pattern = harness.metric_reader("shiftbank_roofline").PATTERN
    for lowered in (
        ops._shiftgroups_jit.lower(spec, t, d, False, groups),
        ops._shiftgroups_multibank_jit.lower(spec, (t, t), (d, d), False, (groups, groups)),
    ):
        names = _kernel_names(lowered)
        assert names and all(re.search(pattern, n) for n in names), names
