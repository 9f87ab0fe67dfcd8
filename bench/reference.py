"""Plain reference for the QuClassi cells, independent of the program.

Straightforward statevector simulation in ``jax.numpy`` of the circuit that
DQuLearn (arXiv:2312.08158, Sec. III-IV) trains: an ancilla (qubit 0, the
most significant bit of the basis index), a data register of m qubits
encoded with RX then RY per qubit, a trainable register of m qubits built
from the QuClassi layers

  single    RY, RZ on every trainable qubit
  dual      RYY, RZZ on adjacent trainable pairs
  entangle  CRY, CRZ on adjacent trainable pairs (control = lower qubit)

and a SWAP test (H, CSWAP(ancilla, d_i, t_i) for each i, H).  The fidelity
is F = clip(2 P(ancilla = 0) - 1, 0, 1).  Amplitudes are (re, im) pairs of
the given dtype: float32 with ``Precision.HIGHEST`` is the reference,
bfloat16 is the control that a correct comparison must reject.

The classifier around it (Algorithm 1): images are cleaned (clip at the
99.5th percentile, rescale to [0, 1]), cut into 4x4 patches at stride 2
with zero padding, each patch is encoded as pi * sigmoid(patch @ w + b), a
class score is the mean patch fidelity against that class's trainable
register, and the loss is the mean one-vs-all binary cross-entropy.
Trainable angles get the two-term parameter-shift gradient (Algorithm 1
uses it for every parameter, the controlled rotations included); the dense
layer gets the exact chain-rule gradient.  SGD applies both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.lax import Precision

SHIFT = math.pi / 2
EPS = 1e-7  # fidelity clip inside the cross-entropy


# ------------------------------------------------------------------ circuit
def circuit(n_qubits: int, n_layers: int) -> tuple:
    """Gate list ``(gate, qubits, angle)``; ``angle`` is ``("theta", j)``,
    ``("data", j)`` or None."""
    m = (n_qubits - 1) // 2
    data_q = list(range(1, 1 + m))
    train_q = list(range(1 + m, 1 + 2 * m))
    ops = []
    d = 0
    for q in data_q:
        ops += [("rx", (q,), ("data", d)), ("ry", (q,), ("data", d + 1))]
        d += 2
    t = 0
    pairs = list(zip(train_q[:-1], train_q[1:]))
    for layer in ("single", "dual", "entangle")[:n_layers]:
        if layer == "single":
            for q in train_q:
                ops += [("ry", (q,), ("theta", t)), ("rz", (q,), ("theta", t + 1))]
                t += 2
        else:
            a, b = ("ryy", "rzz") if layer == "dual" else ("cry", "crz")
            for p in pairs:
                ops += [(a, p, ("theta", t)), (b, p, ("theta", t + 1))]
                t += 2
    ops.append(("h", (0,), None))
    for dq, tq in zip(data_q, train_q):
        ops.append(("cswap", (0, dq, tq), None))
    ops.append(("h", (0,), None))
    return tuple(ops)


def circuit_of(cfg: dict) -> tuple:
    """The circuit a configuration file describes."""
    return circuit(cfg["n_qubits"], cfg["n_layers"])


def params_of(cfg: dict, seed: int) -> dict:
    """Parameters of a configuration's classifier, drawn from ``seed``."""
    return init_params(seed, cfg["n_classes"], cfg["n_theta"], cfg["patch_dim"], cfg["n_data"])


def _matrix(gate: str, angle, dtype):
    """(B, 2**k, 2**k) real and imaginary parts of one gate, per lane."""
    if angle is None:
        if gate == "h":
            u = np.array([[1, 1], [1, -1]]) / math.sqrt(2)
        else:  # cswap: swap |1ab> and |1ba>
            u = np.eye(8)
            u[[5, 6]] = u[[6, 5]]
        re = jnp.asarray(u, dtype)[None]
        return re, jnp.zeros_like(re)
    c = jnp.cos(angle / 2).astype(dtype)
    s = jnp.sin(angle / 2).astype(dtype)
    o, z = jnp.ones_like(c), jnp.zeros_like(c)

    def mat(rows):
        return jnp.stack([jnp.stack(r, -1) for r in rows], -2)

    if gate == "rx":
        return mat([[c, z], [z, c]]), mat([[z, -s], [-s, z]])
    if gate == "ry":
        return mat([[c, -s], [s, c]]), mat([[z, z], [z, z]])
    if gate == "rz":
        return mat([[c, z], [z, c]]), mat([[-s, z], [z, s]])
    if gate == "ryy":  # exp(-i t/2 Y(x)Y)
        re = mat([[c, z, z, z], [z, c, z, z], [z, z, c, z], [z, z, z, c]])
        im = mat([[z, z, z, s], [z, z, -s, z], [z, -s, z, z], [s, z, z, z]])
        return re, im
    if gate == "rzz":  # exp(-i t/2 Z(x)Z)
        re = mat([[c, z, z, z], [z, c, z, z], [z, z, c, z], [z, z, z, c]])
        im = mat([[-s, z, z, z], [z, s, z, z], [z, z, s, z], [z, z, z, -s]])
        return re, im
    if gate in ("cry", "crz"):  # diag(I, R)
        if gate == "cry":
            re = mat([[o, z, z, z], [z, o, z, z], [z, z, c, -s], [z, z, s, c]])
            im = jnp.zeros_like(re)
        else:
            re = mat([[o, z, z, z], [z, o, z, z], [z, z, c, z], [z, z, z, c]])
            im = mat([[z, z, z, z], [z, z, z, z], [z, z, -s, z], [z, z, z, s]])
        return re, im
    raise ValueError(gate)


def _apply(re, im, u_re, u_im, qubits, n):
    """Apply a k-qubit gate (per-lane matrices) to (B, 2**n) states."""
    b = re.shape[0]
    k = len(qubits)
    axes = [1 + q for q in qubits]
    rest = [1 + q for q in range(n) if q not in qubits]
    perm = [0] + rest + axes
    inv = np.argsort(perm)

    def to_mat(x):
        return jnp.transpose(x.reshape((b,) + (2,) * n), perm).reshape(b, -1, 2**k)

    def back(x):
        return jnp.transpose(x.reshape((b,) + (2,) * n), inv).reshape(b, 2**n)

    xr, xi = to_mat(re), to_mat(im)
    prec = Precision.HIGHEST if re.dtype == jnp.float32 else Precision.DEFAULT
    mm = functools.partial(jnp.einsum, "brj,bij->bri", precision=prec)
    ur = jnp.broadcast_to(u_re, (b,) + u_re.shape[1:])
    ui = jnp.broadcast_to(u_im, (b,) + u_im.shape[1:])
    return back(mm(xr, ur) - mm(xi, ui)), back(mm(xr, ui) + mm(xi, ur))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _fidelity_block(ops: tuple, theta, data, dtype):
    """Fidelity of each row: simulate the whole circuit from |0...0>, read
    the ancilla."""
    n = 1 + max(q for op in ops for q in op[1])
    b = theta.shape[0]
    re = jnp.zeros((b, 2**n), dtype).at[:, 0].set(1)
    im = jnp.zeros((b, 2**n), dtype)
    for gate, qubits, angle in ops:
        a = None
        if angle is not None:
            a = (theta if angle[0] == "theta" else data)[:, angle[1]].astype(jnp.float32)
        u_re, u_im = _matrix(gate, a, dtype)
        re, im = _apply(re, im, u_re, u_im, qubits, n)
    p = re.astype(jnp.float32) ** 2 + im.astype(jnp.float32) ** 2
    p0 = p[:, : 2 ** (n - 1)].sum(-1)
    return jnp.clip(2.0 * p0 - 1.0, 0.0, 1.0)


def fidelities(ops: tuple, theta, data, dtype=jnp.float32, block: int = 4096):
    """Fidelity of every row: theta (N, P), data (N, D) -> (N,) float32,
    computed in blocks of ``block`` rows."""
    theta = jnp.asarray(theta, jnp.float32)
    data = jnp.asarray(data, jnp.float32)
    n = theta.shape[0]
    outs = []
    for i in range(0, n, block):
        t, d = theta[i : i + block], data[i : i + block]
        pad = block - t.shape[0] if n > block else 0
        if pad:
            t = jnp.pad(t, ((0, pad), (0, 0)))
            d = jnp.pad(d, ((0, pad), (0, 0)))
        f = _fidelity_block(ops, t, d, dtype)
        outs.append(f[: f.shape[0] - pad])
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


# --------------------------------------------------------------- classifier
def init_params(seed: int, n_classes: int, n_theta_: int, patch_dim: int, n_angles: int):
    """theta ~ U[0, pi) per class; dense w ~ N(0, 1/patch_dim); b = 0."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "theta": jax.random.uniform(k1, (n_classes, n_theta_), minval=0.0, maxval=jnp.pi),
        "w": jax.random.normal(k2, (patch_dim, n_angles)) * (1.0 / jnp.sqrt(patch_dim)),
        "b": jnp.zeros((n_angles,)),
    }


def clean(images: np.ndarray) -> np.ndarray:
    hi = np.percentile(images, 99.5)
    return (np.clip(images, 0.0, hi) / max(hi, 1e-8)).astype(np.float32)


def patch_grid(size: int, width: int = 4, stride: int = 2) -> int:
    """Patches along one side of a ``size`` image, the last one zero-padded."""
    return max(1, -(-(size - width) // stride) + 1)


def patches(images, width: int = 4, stride: int = 2):
    """(B, H, W) -> (B, n_patches, width**2), row-major, zero-padded."""
    b, h, w = images.shape
    ph, pw = patch_grid(h, width, stride), patch_grid(w, width, stride)
    x = jnp.pad(
        jnp.asarray(images),
        ((0, 0), (0, (ph - 1) * stride + width - h), (0, (pw - 1) * stride + width - w)),
    )
    rows = [
        x[:, i * stride : i * stride + width, j * stride : j * stride + width].reshape(b, -1)
        for i in range(ph)
        for j in range(pw)
    ]
    return jnp.stack(rows, 1)


def encode(params, images):
    """(B, H, W) images -> (B * n_patches, n_angles) data angles."""
    x = patches(images)
    z = jnp.einsum("bpk,ka->bpa", x, params["w"], precision=Precision.HIGHEST) + params["b"]
    return (jnp.pi * jax.nn.sigmoid(z)).reshape(-1, params["w"].shape[1])


def bce(f, y):
    f = jnp.clip(f, EPS, 1.0 - EPS)
    return -(y * jnp.log(f) + (1.0 - y) * jnp.log(1.0 - f))


def _class_scores(ops, params, images, dtype):
    """(B, C) mean patch fidelity per image and class."""
    b = images.shape[0]
    angles = encode(params, images)
    per = angles.shape[0] // b
    cols = []
    for theta in params["theta"]:
        t = jnp.broadcast_to(theta, (angles.shape[0], theta.shape[0]))
        cols.append(_fidelity_block(ops, t, angles, dtype).reshape(b, per).mean(-1))
    return jnp.stack(cols, -1)


def loss_and_grads(ops, params, images, labels, dtype=jnp.float32):
    """Loss of one step, the two-term shift gradient of every theta, and
    the chain-rule gradient of the dense layer."""
    images = jnp.asarray(images)
    labels = jnp.asarray(labels)
    n_cls, p = params["theta"].shape
    onehot = jax.nn.one_hot(labels, n_cls)
    b = images.shape[0]
    angles = encode(params, images)
    per = angles.shape[0] // b
    lanes = angles.shape[0]
    losses, grads = [], []
    for c in range(n_cls):
        theta = params["theta"][c]
        shifted = [theta] + [theta.at[j].add(s) for s in (SHIFT, -SHIFT) for j in range(p)]
        t = jnp.concatenate([jnp.broadcast_to(x, (lanes, p)) for x in shifted])
        d = jnp.tile(angles, (len(shifted), 1))
        f = fidelities(ops, t, d, dtype).reshape(len(shifted), b, per)
        f0 = f[0].mean(-1)
        df = ((f[1 : 1 + p] - f[1 + p :]) / 2.0).mean(-1)  # (P, B)
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
