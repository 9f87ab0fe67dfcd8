"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and raises what the chip's compiler
would raise (unaligned slices, unlowerable primitives, VMEM overflow).
Interpret-mode tests cannot see any of that.  Each case compiles one
launch at the paper's widest width and checks that the compiled program
holds a Mosaic kernel (``tpu_custom_call``), not interpreter HLO.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import circuits
from repro.kernels import ops as kops
from repro.kernels import vqc_statevector as K

TB = 4 * K.LANES


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
def compiled_kernels(monkeypatch):
    """Steer the kernels off the CPU interpreter: the process's backend is
    the CPU, the compile target is the described TPU."""
    monkeypatch.setattr(K, "resolve_interpret", lambda interpret: False)


def _angles(sharding, rows, spec):
    sds = lambda k: jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=sharding)  # noqa: E731
    return sds(spec.n_theta), sds(spec.n_data)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_vqc_p0_7q3l(one_chip, compiled_kernels):
    spec = circuits.build_quclassi_circuit(7, 3)
    _assert_mosaic(lambda t, d: K.vqc_p0(spec, t, d, tb=TB), *_angles(one_chip, TB, spec))


def test_shift_fidelity_fused_7q3l(one_chip, compiled_kernels):
    spec = circuits.build_quclassi_circuit(7, 3)
    assert K.shift_execution_info(spec, TB)["mode"] == "fused"
    _assert_mosaic(
        lambda t, d: K.vqc_shift_fidelity(spec, t, d), *_angles(one_chip, TB, spec)
    )


def test_shift_fidelity_spilled_17q3l(one_chip, compiled_kernels):
    spec = circuits.build_quclassi_circuit(17, 3)
    assert K.shift_execution_info(spec, TB)["mode"] == "spill"
    _assert_mosaic(
        lambda t, d: K.vqc_shift_fidelity(spec, t, d), *_angles(one_chip, TB, spec)
    )


def test_multibank_k4_7q3l(one_chip, compiled_kernels):
    spec = circuits.build_quclassi_circuit(7, 3)
    n_groups = 1 + 2 * spec.n_theta
    banks = [_angles(one_chip, K.LANES, spec) for _ in range(4)]
    # a fresh jit of the launch body, so no compiled-for-TPU trace lands in
    # the shared wrapper's cache
    launch = jax.jit(
        kops._shiftgroups_multibank_jit.__wrapped__, static_argnums=(0, 3, 4)
    )
    text = (
        launch.lower(
            spec,
            tuple(t for t, _ in banks),
            tuple(d for _, d in banks),
            False,
            (tuple(range(n_groups)),) * 4,
        )
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in text
