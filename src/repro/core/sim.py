"""Pure-JAX statevector simulator over (re, im) float32 pairs.

This is the reference data plane for DQuLearn: exact statevector simulation
of the few-qubit circuits the paper distributes (5 and 7 qubits in the
evaluation; anything up to ~20 qubits is fine on one device).

Layout convention: a state over ``n`` qubits is a pair of float32 arrays of
shape ``(..., 2**n)`` (leading axes = batch).  Qubit 0 is the MOST significant
bit of the basis index, matching how circuit diagrams are usually read
top-down: basis index = q0 q1 ... q_{n-1} in binary.

All functions are jit/vmap/grad-compatible; the circuit structure is static
Python, the angles are traced.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax.numpy as jnp
from jax.lax import Precision

from repro.core import gates as G

State = tuple[jnp.ndarray, jnp.ndarray]


def zero_state(n_qubits: int, batch: tuple[int, ...] = ()) -> State:
    dim = 2**n_qubits
    re = jnp.zeros(batch + (dim,), jnp.float32).at[..., 0].set(1.0)
    im = jnp.zeros(batch + (dim,), jnp.float32)
    return re, im


def apply_gate(state: State, u: G.Mat, qubits: Sequence[int], n_qubits: int) -> State:
    """Apply a k-qubit gate ``u`` to ``qubits`` of an n-qubit state.

    Works by viewing the state as a rank-n tensor of shape (2,)*n, moving the
    target axes to the front, contracting with the (2**k, 2**k) matrix, and
    moving axes back.  Batch axes are preserved.
    """
    re, im = state
    k = len(qubits)
    batch = re.shape[:-1]
    nb = len(batch)
    t = re.reshape(batch + (2,) * n_qubits), im.reshape(batch + (2,) * n_qubits)

    axes = [nb + q for q in qubits]
    rest = [nb + i for i in range(n_qubits) if i not in set(qubits)]
    perm = list(range(nb)) + axes + rest
    t_re = jnp.transpose(t[0], perm).reshape(batch + (2**k, -1))
    t_im = jnp.transpose(t[1], perm).reshape(batch + (2**k, -1))

    u_re, u_im = u
    # complex matmul: (U_re + i U_im) @ (t_re + i t_im).  HIGHEST keeps the
    # reference exact in f32 on TPU, where the default is one bf16 pass.
    mm = functools.partial(
        jnp.einsum, "ij,...jk->...ik", precision=Precision.HIGHEST
    )
    o_re = mm(u_re, t_re) - mm(u_im, t_im)
    o_im = mm(u_re, t_im) + mm(u_im, t_re)

    o_re = o_re.reshape(batch + (2,) * n_qubits)
    o_im = o_im.reshape(batch + (2,) * n_qubits)
    inv = [0] * (nb + n_qubits)
    for i, p in enumerate(perm):
        inv[p] = i
    o_re = jnp.transpose(o_re, inv).reshape(batch + (2**n_qubits,))
    o_im = jnp.transpose(o_im, inv).reshape(batch + (2**n_qubits,))
    return o_re, o_im


# ------------------------------------------------------------- circuit spec
@dataclasses.dataclass(frozen=True)
class Op:
    """One gate in a circuit.

    ``param`` selects the angle source:
      ("theta", j)  -> trainable parameter j
      ("data", j)   -> data-encoding angle j
      ("const", v)  -> fixed float angle v
      None          -> non-parameterized gate
    """
    gate: str
    qubits: tuple[int, ...]
    param: tuple | None = None

    def __post_init__(self):
        ctor, k, takes_angle = G.GATES[self.gate]
        assert len(self.qubits) == k, (self.gate, self.qubits)
        assert takes_angle == (self.param is not None), (self.gate, self.param)


@dataclasses.dataclass(frozen=True)
class CircuitSpec:
    """Static circuit structure: gates are Python data, angles are traced."""
    n_qubits: int
    ops: tuple[Op, ...]
    n_theta: int
    n_data: int

    def angle_of(self, op: Op, theta, data):
        kind, j = op.param
        if kind == "theta":
            return theta[..., j]
        if kind == "data":
            return data[..., j]
        if kind == "const":
            return jnp.asarray(j, jnp.float32)
        raise ValueError(op.param)


def run_circuit(spec: CircuitSpec, theta, data, state: State | None = None) -> State:
    """Execute ``spec`` from |0...0> (or ``state``). theta: (n_theta,), data: (n_data,)."""
    if state is None:
        state = zero_state(spec.n_qubits)
    for op in spec.ops:
        ctor, _, takes_angle = G.GATES[op.gate]
        u = ctor(spec.angle_of(op, theta, data)) if takes_angle else ctor()
        state = apply_gate(state, u, op.qubits, spec.n_qubits)
    return state


def probabilities(state: State) -> jnp.ndarray:
    re, im = state
    return re * re + im * im


def marginal_p0(state: State, qubit: int, n_qubits: int) -> jnp.ndarray:
    """P(measuring |0> on ``qubit``)."""
    p = probabilities(state)
    batch = p.shape[:-1]
    t = p.reshape(batch + (2,) * n_qubits)
    t = jnp.moveaxis(t, len(batch) + qubit, len(batch))
    return t.reshape(batch + (2, -1))[..., 0, :].sum(-1)


def state_norm(state: State) -> jnp.ndarray:
    return jnp.sqrt(probabilities(state).sum(-1))
