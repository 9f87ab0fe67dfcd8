"""Plain reference for QuClassi cells whose registers are wide, independent
of the program.

The same classifier and training step as ``bench/reference.py`` (Algorithm
1 of DQuLearn, arXiv:2312.08158: RX then RY encoding of each data qubit,
the single / dual / entangle trainable layers, a SWAP test, the mean patch
fidelity as the class score, one-vs-all cross-entropy, the two-term shift
gradient for every trainable angle, the chain rule for the dense layer,
SGD), with one departure: the SWAP-test identity
P(ancilla = 0) = (1 + |<phi|psi>|^2) / 2 takes the place of simulating the
ancilla and the CSWAPs.  Each of the two m-qubit registers is simulated
on its own, 2**m amplitudes, and F = |<phi(data)|psi(theta)>|^2 is read
from their overlap: at 17 qubits a whole-circuit statevector per row would
be 2**17 amplitudes, a register 256.

Amplitudes are (re, im) pairs of the given dtype: float32 with
``Precision.HIGHEST`` is the reference, bfloat16 the control that a
correct comparison must reject.  The API is ``bench/reference.py``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.lax import Precision

from bench.reference import (
    EPS,
    SHIFT,
    _apply,
    _matrix,
    bce,
    circuit,
    circuit_of,
    clean,
    encode,
    params_of,
    patch_grid,
)

__all__ = ["circuit", "circuit_of", "params_of", "patch_grid", "registers",
           "fidelities", "loss_and_grads", "sgd_trajectory"]


# ---------------------------------------------------------------- registers
def registers(ops: tuple) -> tuple[int, tuple, tuple]:
    """(m, data-register ops, trainable-register ops) of a QuClassi circuit:
    qubit 0 the ancilla, qubits 1..m the data register, m+1..2m the
    trainable one; each op's qubits are renumbered from 0 within its
    register, and the SWAP test is left out."""
    n = 1 + max(q for op in ops for q in op[1])
    m = (n - 1) // 2
    data, train = [], []
    for gate, qubits, angle in ops:
        if gate in ("h", "cswap"):
            continue
        if all(1 <= q <= m for q in qubits):
            data.append((gate, tuple(q - 1 for q in qubits), angle))
        else:
            train.append((gate, tuple(q - 1 - m for q in qubits), angle))
    return m, tuple(data), tuple(train)


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _register_states(reg_ops: tuple, m: int, angles, dtype):
    """(N, 2**m) re and im of one register from |0...0>, one row of
    ``angles`` (theta or data, whichever the register reads) per state."""
    b = angles.shape[0]
    re = jnp.zeros((b, 2**m), dtype).at[:, 0].set(1)
    im = jnp.zeros((b, 2**m), dtype)
    for gate, qubits, angle in reg_ops:
        u_re, u_im = _matrix(gate, angles[:, angle[1]].astype(jnp.float32), dtype)
        re, im = _apply(re, im, u_re, u_im, qubits, m)
    return re, im


def _f32(state):
    return tuple(x.astype(jnp.float32) for x in state)


def _overlaps(phi, psi):
    """|<phi_n|psi_c>|^2 for every pair: phi (N, 2**m), psi (C, 2**m)
    (re, im) pairs -> (N, C) float32."""
    (p_re, p_im), (q_re, q_im) = _f32(phi), _f32(psi)
    dot = functools.partial(jnp.einsum, "na,ca->nc", precision=Precision.HIGHEST)
    ip_re = dot(p_re, q_re) + dot(p_im, q_im)
    ip_im = dot(p_re, q_im) - dot(p_im, q_re)
    return jnp.clip(ip_re**2 + ip_im**2, 0.0, 1.0)


def _data_states(ops, data, dtype):
    m, data_ops, _ = registers(ops)
    return _register_states(data_ops, m, jnp.asarray(data, jnp.float32), dtype)


def _train_states(ops, theta, dtype):
    m, _, train_ops = registers(ops)
    return _register_states(train_ops, m, jnp.asarray(theta, jnp.float32), dtype)


def fidelities(ops: tuple, theta, data, dtype=jnp.float32, block: int = 4096):
    """Fidelity of every row: theta (N, P), data (N, D) -> (N,) float32,
    computed in blocks of ``block`` rows."""
    theta = jnp.asarray(theta, jnp.float32)
    data = jnp.asarray(data, jnp.float32)
    outs = []
    for i in range(0, theta.shape[0], block):
        p_re, p_im = _f32(_data_states(ops, data[i : i + block], dtype))
        q_re, q_im = _f32(_train_states(ops, theta[i : i + block], dtype))
        ip_re = (p_re * q_re + p_im * q_im).sum(-1)
        ip_im = (p_re * q_im - p_im * q_re).sum(-1)
        outs.append(jnp.clip(ip_re**2 + ip_im**2, 0.0, 1.0))
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


# --------------------------------------------------------------- classifier
def _class_scores(ops, params, images, dtype):
    """(B, C) mean patch fidelity per image and class."""
    b = images.shape[0]
    f = _overlaps(_data_states(ops, encode(params, images), dtype),
                  _train_states(ops, params["theta"], dtype))
    return f.reshape(b, -1, f.shape[-1]).mean(1)


def loss_and_grads(ops, params, images, labels, dtype=jnp.float32):
    """Loss of one step, the two-term shift gradient of every theta, and
    the chain-rule gradient of the dense layer."""
    images = jnp.asarray(images)
    labels = jnp.asarray(labels)
    n_cls, p = params["theta"].shape
    onehot = jax.nn.one_hot(labels, n_cls)
    b = images.shape[0]
    phi = _data_states(ops, encode(params, images), dtype)
    losses, grads = [], []
    for c in range(n_cls):
        theta = params["theta"][c]
        shifted = jnp.stack(
            [theta] + [theta.at[j].add(s) for s in (SHIFT, -SHIFT) for j in range(p)]
        )
        f = _overlaps(phi, _train_states(ops, shifted, dtype))    # (B*Np, 1+2P)
        f = f.T.reshape(1 + 2 * p, b, -1)
        f0 = f[0].mean(-1)
        df = ((f[1 : 1 + p] - f[1 + p :]) / 2.0).mean(-1)        # (P, B)
        fc = jnp.clip(f0, EPS, 1.0 - EPS)
        chain = (fc - onehot[:, c]) / (fc * (1.0 - fc))
        grads.append((df * chain[None]).mean(-1) / n_cls)
        losses.append(bce(f0, onehot[:, c]).mean())

    def dense_loss(wb):
        scores = _class_scores(ops, dict(params, **wb), images, dtype)
        return bce(scores, onehot).mean()

    g_dense = jax.grad(dense_loss)({"w": params["w"], "b": params["b"]})
    return jnp.stack(losses).mean(), dict(theta=jnp.stack(grads), **g_dense)


def sgd_trajectory(ops, params, images, labels, *, batch: int, lr: float, seed: int,
                   steps: int, dtype=jnp.float32, half_batch: bool = False):
    """Follow ``steps`` SGD steps of one epoch over ``images``, in the feed
    order of Algorithm 1's trainer (one permutation drawn from
    ``seed * 997``).  Returns (per-step losses, first gradient, params)."""
    x = clean(np.asarray(images))
    y = np.asarray(labels)
    order = np.random.default_rng(seed * 997).permutation(len(y))
    losses, first = [], None
    for i in range(steps):
        idx = order[i * batch : (i + 1) * batch]
        if half_batch:  # a fault: half of the batch left out
            idx = idx[: batch // 2]
        loss, g = loss_and_grads(ops, params, x[idx], y[idx], dtype)
        losses.append(float(loss))
        first = g if first is None else first
        params = jax.tree.map(lambda p_, g_: p_ - lr * g_, params, g)
    return losses, first, params
