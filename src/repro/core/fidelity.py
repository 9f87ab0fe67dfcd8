"""SWAP-test fidelity readout + fidelity-based loss (Quantum Measurement +
Quantum State Analyst modules of the paper's architecture, Fig 1).

After the SWAP test, P(ancilla = 0) = (1 + F) / 2 where
F = |<data|trainable>|^2, so F = 2 P0 - 1.  The paper's Quantum Measurement
module "calculates the fidelity from one ancilla qubit which is used to
calculate model loss".

Because that identity is exact, F can also be read from the two m-qubit
registers the SWAP test compares (``register_specs``,
``register_fidelities``): a data-register state per sample and one
trainable-register state per parameter vector, 2**m amplitudes each,
instead of the whole 2**(2m+1)-amplitude circuit per sample.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.lax import Precision

from repro.core import sim
from repro.core.sim import CircuitSpec

_EPS = 1e-7


def ancilla_p0(spec: CircuitSpec, theta, data) -> jnp.ndarray:
    state = sim.run_circuit(spec, theta, data)
    return sim.marginal_p0(state, qubit=0, n_qubits=spec.n_qubits)


def fidelity(spec: CircuitSpec, theta, data) -> jnp.ndarray:
    """F = |<phi(data)|psi(theta)>|^2 in [0, 1] via the SWAP test."""
    return jnp.clip(2.0 * ancilla_p0(spec, theta, data) - 1.0, 0.0, 1.0)


def fidelity_batch(spec: CircuitSpec, theta, data) -> jnp.ndarray:
    """vmap over leading batch axes of both theta and data: (B,P),(B,D)->(B,)."""
    return jax.vmap(lambda t, d: fidelity(spec, t, d))(theta, data)


@functools.lru_cache(maxsize=None)
def register_specs(spec: CircuitSpec) -> tuple[CircuitSpec, CircuitSpec]:
    """The two m-qubit registers a SWAP-test circuit compares, as circuits
    of their own: ``(data_spec, train_spec)``, each op remapped to
    register-local qubits.  The circuit has to end in H(anc),
    CSWAP(anc, d_i, t_i) for i < m, H(anc), with every earlier op inside
    one register, no trainable angle on the data register and no data
    angle on the trainable one; ValueError otherwise."""
    ops = spec.ops
    anc = ops[-1].qubits[0]
    k = len(ops) - 2
    pairs = []
    while k > 0 and ops[k].gate == "cswap" and ops[k].qubits[0] == anc:
        pairs.insert(0, ops[k].qubits[1:])
        k -= 1
    data_q = {q: i for i, (q, _) in enumerate(pairs)}
    train_q = {q: i for i, (_, q) in enumerate(pairs)}
    regs = {"data": (data_q, []), "theta": (train_q, [])}
    ok = (
        pairs
        and ops[-1].gate == ops[k].gate == "h"
        and ops[k].qubits == (anc,)
        and len(set(data_q) | set(train_q) | {anc}) == spec.n_qubits
    )
    for op in ops[:k] if ok else ():
        kind = "data" if set(op.qubits) <= set(data_q) else "theta"
        qmap, body = regs[kind]
        ok = set(op.qubits) <= set(qmap) and (
            op.param is None or op.param[0] in (kind, "const")
        )
        if not ok:
            break
        body.append(dataclasses.replace(op, qubits=tuple(qmap[q] for q in op.qubits)))
    if not ok:
        raise ValueError("circuit does not end in a SWAP test of two registers")
    m = len(pairs)
    return tuple(
        CircuitSpec(m, tuple(regs[kind][1]), spec.n_theta, spec.n_data)
        for kind in ("data", "theta")
    )


def register_fidelities(
    regs: tuple[CircuitSpec, CircuitSpec], theta, data
) -> jnp.ndarray:
    """F = |<phi(data_n)|psi(theta_c)>|^2 for every pair: theta (C, P),
    data (N, D) -> (N, C).  Each register is simulated once per row of its
    own angles with ``sim.run_circuit``; exact where ``fidelity`` is, since
    the SWAP test reads this same overlap."""
    data_spec, train_spec = regs
    no_theta = jnp.zeros((data_spec.n_theta,), jnp.float32)
    no_data = jnp.zeros((train_spec.n_data,), jnp.float32)
    p_re, p_im = jax.vmap(lambda d: sim.run_circuit(data_spec, no_theta, d))(data)
    q_re, q_im = jax.vmap(lambda t: sim.run_circuit(train_spec, t, no_data))(theta)
    dot = lambda a, b: jnp.einsum(  # noqa: E731
        "na,ca->nc", a, b, precision=Precision.HIGHEST
    )
    ip_re = dot(p_re, q_re) + dot(p_im, q_im)                  # <phi|psi>
    ip_im = dot(p_re, q_im) - dot(p_im, q_re)
    return jnp.clip(ip_re * ip_re + ip_im * ip_im, 0.0, 1.0)


def bce_loss(fid: jnp.ndarray, label: jnp.ndarray) -> jnp.ndarray:
    """Binary cross-entropy with fidelity as p(class=1) (QuClassi's loss)."""
    f = jnp.clip(fid, _EPS, 1.0 - _EPS)
    return -(label * jnp.log(f) + (1.0 - label) * jnp.log(1.0 - f))


def bce_grad_wrt_fidelity(fid: jnp.ndarray, label: jnp.ndarray) -> jnp.ndarray:
    """dL/dF, evaluated classically by the Quantum State Analyst."""
    f = jnp.clip(fid, _EPS, 1.0 - _EPS)
    return (f - label) / (f * (1.0 - f))
