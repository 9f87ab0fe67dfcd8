"""The plain reference agrees with the program's own simulator, which it
does not import, and reads visibly worse in bfloat16."""
import jax
import numpy as np
import pytest

from bench import reference


def test_matches_the_program_simulator():
    from repro.core import circuits, fidelity

    spec = circuits.build_quclassi_circuit(7, 3)
    rng = np.random.default_rng(0)
    theta = rng.uniform(0, np.pi, (32, spec.n_theta)).astype(np.float32)
    data = rng.uniform(0, np.pi, (32, spec.n_data)).astype(np.float32)
    want = np.asarray(jax.jit(fidelity.fidelity_batch, static_argnums=0)(spec, theta, data))
    got = np.asarray(reference.fidelities(reference.circuit(7, 3), theta, data))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_bfloat16_is_visibly_worse():
    circ = reference.circuit(7, 3)
    rng = np.random.default_rng(1)
    theta = rng.uniform(0, np.pi, (256, 14)).astype(np.float32)
    data = rng.uniform(0, np.pi, (256, 6)).astype(np.float32)
    f32 = np.asarray(reference.fidelities(circ, theta, data))
    bf16 = np.asarray(reference.fidelities(circ, theta, data, dtype=jax.numpy.bfloat16))
    assert np.abs(f32 - bf16).max() > 1e-3
