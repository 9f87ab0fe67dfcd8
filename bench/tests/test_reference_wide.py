"""The register reference agrees with the whole-circuit reference, which
simulates the ancilla and the SWAP test, and reads visibly worse in
bfloat16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import digits, reference, reference_wide

#: relative gap of each gradient leaf: the whole-circuit reference reads F
#: as 2 P0 - 1, which at 9 qubits loses about 3e-5 of the gradient to f32
#: cancellation (the register reference is within 1e-6 of a float64
#: evaluation there), so the wider circuit gets the wider bound
GRAD_GAP = {5: 1e-5, 7: 1e-5, 9: 1e-4}


def _inputs(n, seed):
    m = (n - 1) // 2
    cfg = {"n_classes": 2, "n_theta": 2 * m + 4 * (m - 1), "patch_dim": 16, "n_data": 2 * m}
    rng = np.random.default_rng(seed)
    return cfg, rng


@pytest.mark.parametrize("n", [5, 7, 9])
def test_fidelities_match(n):
    cfg, rng = _inputs(n, n)
    ops = reference.circuit(n, 3)
    assert reference_wide.circuit_of({"n_qubits": n, "n_layers": 3}) == ops
    theta = rng.uniform(0, np.pi, (64, cfg["n_theta"])).astype(np.float32)
    data = rng.uniform(0, np.pi, (64, cfg["n_data"])).astype(np.float32)
    want = np.asarray(reference.fidelities(ops, theta, data))
    got = np.asarray(reference_wide.fidelities(ops, theta, data, block=48))
    assert np.abs(got - want).max() <= 1e-6


@pytest.mark.parametrize("n", [5, 7, 9])
def test_loss_and_gradients_match(n):
    cfg, rng = _inputs(n, 10 + n)
    ops = reference.circuit(n, 3)
    images, labels = digits.pair_set(3, 9, 8, rng)
    p = reference.params_of(cfg, 3)
    assert jax.tree.all(jax.tree.map(jnp.array_equal, reference_wide.params_of(cfg, 3), p))
    x = reference.clean(images)
    l_want, g_want = reference.loss_and_grads(ops, p, x, labels)
    l_got, g_got = reference_wide.loss_and_grads(ops, p, x, labels)
    assert abs(float(l_got) - float(l_want)) <= 1e-5 * abs(float(l_want))
    for k in g_want:
        gap = float(jnp.linalg.norm(g_got[k] - g_want[k]) / jnp.linalg.norm(g_want[k]))
        assert gap <= GRAD_GAP[n], (k, gap)


def test_sgd_trajectory_matches():
    n = 7
    cfg, rng = _inputs(n, 3)
    ops = reference.circuit(n, 3)
    images, labels = digits.pair_set(3, 9, 8, rng)
    p = reference.params_of(cfg, 5)
    kw = dict(batch=4, lr=0.05, seed=9, steps=2)
    ls_w, g_w, p_w = reference.sgd_trajectory(ops, p, images, labels, **kw)
    ls_g, g_g, p_g = reference_wide.sgd_trajectory(ops, p, images, labels, **kw)
    np.testing.assert_allclose(ls_g, ls_w, rtol=1e-5)
    for k in p:
        np.testing.assert_allclose(np.asarray(p_g[k]), np.asarray(p_w[k]), atol=1e-6)


def test_bfloat16_is_visibly_worse():
    ops = reference.circuit(17, 3)
    rng = np.random.default_rng(1)
    theta = rng.uniform(0, np.pi, (256, 44)).astype(np.float32)
    data = rng.uniform(0, np.pi, (256, 16)).astype(np.float32)
    f32 = np.asarray(reference_wide.fidelities(ops, theta, data))
    bf16 = np.asarray(reference_wide.fidelities(ops, theta, data, dtype=jnp.bfloat16))
    assert np.abs(f32 - bf16).max() > 1e-3
