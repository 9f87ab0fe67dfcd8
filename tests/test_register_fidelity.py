"""The classical half reads F from the two registers the SWAP test
compares, and a QuClassi register too wide for the kernel's VMEM budget
trains through the depth-tiled spill kernel.

Each result is compared with a plain form on seeded random weights: the
full SWAP-test statevector of ``core.sim`` (``fidelity.fidelity_batch``),
``jax.grad`` through it, and ``grad_shift`` with the full-statevector
executor.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ClusterConfig, QuantumCluster, ServingConfig
from repro.core import circuits, fidelity as fid, quclassi, segmentation, shift_rule
from repro.core.quclassi import QuClassiConfig
from repro.core.sim import CircuitSpec, Op
from repro.data import mnist, pipeline
from repro.kernels import vqc_statevector as K


@pytest.fixture(scope="module")
def images():
    x, y = mnist.make_pair_dataset(3, 9, n_per_class=8, seed=1)
    return jnp.asarray(pipeline.clean(x[:6])), jnp.asarray(y[:6])


def _full_class_fidelities(cfg, params, images):
    """(B, C) mean patch fidelity, each patch and class simulated as the
    whole 2**qc-amplitude SWAP-test circuit."""
    patches = segmentation.segment(images, cfg.seg)
    angles = quclassi.encode_patches(cfg, params, patches)
    flat = angles.reshape(-1, angles.shape[-1])

    def per_class(theta):
        t = jnp.broadcast_to(theta, (flat.shape[0],) + theta.shape)
        return fid.fidelity_batch(cfg.spec, t, flat).reshape(images.shape[0], -1).mean(-1)

    return jax.vmap(per_class)(params["theta"]).T


@pytest.mark.parametrize("qc", [5, 7, 9])
def test_register_overlap_matches_swap_test(images, qc):
    cfg = QuClassiConfig(qc=qc, n_layers=3)
    p = quclassi.init_params(cfg, jax.random.PRNGKey(qc))
    data_spec, train_spec = fid.register_specs(cfg.spec)
    assert data_spec.n_qubits == train_spec.n_qubits == (qc - 1) // 2
    got = quclassi.class_fidelities(cfg, p, images[0])
    want = _full_class_fidelities(cfg, p, images[0])
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-6


def test_register_specs_need_a_swap_test_of_two_registers():
    spec = circuits.build_quclassi_circuit(5, 2)
    data_spec, train_spec = fid.register_specs(spec)
    assert len(data_spec.ops) + len(train_spec.ops) == len(spec.ops) - 4
    for ops in (
        spec.ops[:-1],                                   # no closing H
        spec.ops[:-3] + spec.ops[-2:],                   # a CSWAP left out
        (Op("ryy", (1, 3), ("theta", 0)),) + spec.ops,   # straddles registers
        (Op("rx", (3,), ("data", 0)),) + spec.ops,       # data angle, trainable register
    ):
        with pytest.raises(ValueError):
            fid.register_specs(CircuitSpec(spec.n_qubits, ops, spec.n_theta, spec.n_data))


def test_dense_grad_matches_full_simulation_9q3l(images):
    x, y = images
    cfg = QuClassiConfig(qc=9, n_layers=3)
    p = quclassi.init_params(cfg, jax.random.PRNGKey(11))

    def dense_loss(wb):
        f = _full_class_fidelities(cfg, dict(p, **wb), x)
        return quclassi.one_vs_all_loss(f, y)

    want = jax.grad(dense_loss)({"w": p["w"], "b": p["b"]})
    got = quclassi._dense_grad(cfg, p["w"], p["b"], p["theta"], x, y)
    for k in ("w", "b"):
        rel = float(jnp.linalg.norm(got[k] - want[k]) / jnp.linalg.norm(want[k]))
        assert rel <= 1e-5, (k, rel)


def test_classical_half_holds_no_full_state_at_17_qubits():
    """At 17 qubits and 64 images neither compiled program of the
    classical half makes a 2**17-amplitude array."""
    cfg = QuClassiConfig(qc=17, n_layers=3)
    p = quclassi.init_params(cfg, jax.random.PRNGKey(0))
    full = re.compile(rf"\b{2 ** cfg.qc}\b")
    for fn, args in (
        (quclassi._dense_grad,
         (cfg, p["w"], p["b"], p["theta"], jnp.zeros((64, 8, 8)), jnp.zeros(64, jnp.int32))),
        (quclassi.accuracy, (cfg, p, jnp.zeros((16, 8, 8)), jnp.zeros(16, jnp.int32))),
    ):
        lowered = fn.lower(*args)
        assert not full.search(lowered.as_text())
        assert lowered.compile().memory_analysis().temp_size_in_bytes < 2**28


@pytest.fixture
def small_vmem_budget(monkeypatch):
    """A checkpoint budget of 8 register states at one lane tile, so a
    9-qubit bank (m = 4, 20 checkpoints) spills in depth tiles; programs
    traced under it are dropped on both sides."""
    jax.clear_caches()
    monkeypatch.setattr(K, "VMEM_BUDGET_BYTES", (8 + 4) * 2 * 4 * 16 * K.LANES)
    yield
    jax.clear_caches()


def test_spilled_session_train_step_9q3l(images, small_vmem_budget):
    x, y = images
    cfg = QuClassiConfig(qc=9, n_layers=3)
    lr, seed = 0.05, 4
    samples = x.shape[0] * cfg.n_patches
    info = K.shift_execution_info(cfg.spec, samples)
    assert info["mode"] == "spill" and info["n_tiles"] >= 2

    with QuantumCluster(ClusterConfig(serving=ServingConfig(mode="async"))) as cluster:
        sess = cluster.session("wide", bank_mode="implicit")
        rep = sess.train(cfg, (x, y), (x, y), epochs=1, batch_size=x.shape[0], lr=lr, seed=seed)
        tel = cluster.telemetry
        assert tel.trace.kernel_launches.get("spill", 0) >= cfg.n_classes
        assert "fused" not in tel.trace.kernel_launches
        assert "materialize" not in tel.trace.kernel_launches
        assert tel.mesh_spills == 0
        assert tel.spill_banks == cfg.n_classes
        assert tel.spill_tiles == cfg.n_classes * info["n_tiles"]
        assert tel.spill_boundary_bytes == cfg.n_classes * info["spilled_bytes"]

    # the reference: one step of grad_shift on the same images, every
    # circuit simulated whole by core.sim (materialized banks)
    p0 = quclassi.init_params(cfg, jax.random.PRNGKey(seed))
    loss, g, _ = quclassi.grad_shift(
        cfg, p0, x, y, executor=shift_rule.default_executor(cfg.spec), implicit=False
    )
    assert abs(rep.epochs[0].loss - float(loss)) <= 1e-5 * abs(float(loss))
    for k in ("theta", "w", "b"):
        step = (np.asarray(p0[k]) - np.asarray(rep.params[k])) / lr
        np.testing.assert_allclose(step, np.asarray(g[k]), rtol=1e-3, atol=2e-5, err_msg=k)
