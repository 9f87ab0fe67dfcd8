"""Fused VQC statevector Pallas kernel — the DQuLearn compute hot-spot.

The paper's data plane executes millions of *small* circuits (5–7 qubits,
10–20 gates): the circuit bank of parameter-shifted subtasks.  On a GPU/RPC
system each circuit is one round-trip; a mechanical port would launch one
XLA op per gate per circuit.  The TPU-native adaptation is to FUSE the whole
circuit — encoding rotations, variational layers, SWAP test, ancilla readout
— into ONE kernel over a VMEM-resident batch of statevectors:

  * layout: statevectors live as (2**n, TB) tiles — basis index on the
    sublane axis, circuit batch on the 128-wide lane axis.  Gate application
    is a 2x2 (or structured 4x4/8x8) linear combination of ROWS, vectorized
    across lanes; per-circuit angles become per-lane cos/sin vectors.
  * complex numbers are (re, im) float32 pairs (TPU has no complex MXU path).
  * the gate sequence is static Python (unrolled at trace time); angles are
    read from VMEM blocks of the banked parameters.
  * HBM traffic: read (P + D) * TB angle floats, write TB results.  The
    statevector NEVER touches HBM — it is created, evolved and measured in
    VMEM/VREGs.  Per-gate dispatch would move 2 * 4 * 2**n * TB bytes per
    gate; fusion removes all of it (see benchmarks/kernel_bench.py).

VMEM budget: state tile is 2 * 4 * 2**n * TB bytes — for the paper's 7-qubit
circuits and TB=512 that is 512 KB, far under the 16 MiB of VMEM a v5e
kernel may use by default.  Qubit counts up to ~12 fit comfortably (2 * 4 * 4096 * 128 = 4 MB
at TB=128); beyond that, shrink TB.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sim import CircuitSpec

LANES = 128  # TPU lane width; batch tiles are multiples of this.


def kernel_tb(n_lanes: int, tb: int = 4 * LANES) -> int:
    """Lane-tile width a kernel launch picks for an ``n_lanes`` batch: the
    requested ``tb`` shrunk to the batch's power-of-two envelope, never
    below one LANES tile.  The dispatcher's VMEM model MUST use this same
    policy (a divergent copy would silently mis-predict the real kernel
    footprint)."""
    return min(tb, max(LANES, 1 << (max(n_lanes, 1) - 1).bit_length()))


# ----------------------------------------------------------- gate micro-ops
# Each helper operates on (re, im) arrays of shape (rows, TB) and per-lane
# cos/sin vectors of shape (1, TB).  Qubit q is the q-th MOST significant bit
# of the basis (row) index, matching repro.core.sim.
#
# Every gate is written in the three operations Mosaic lowers on any f32
# tile: static sublane rotations (``pltpu.roll``), ``broadcasted_iota`` row
# masks and selects, and elementwise arithmetic.  A one-qubit gate pairs
# row r with its partner r ^ 2**b (b = n-1-q, the qubit's bit); the partner
# tile is one of two rotations of the state by 2**b rows, chosen per row by
# the bit.  No reshape, stack or scatter touches the sublane axis.

#: f32 sublanes per vreg: state tiles are padded up to a multiple of this.
SUBLANES = 8


def state_rows(n: int) -> int:
    """Sublane rows of an n-qubit state tile: 2**n padded up to one full
    f32 vreg tile, so every slice, DMA and reduction starts on a sublane
    boundary.  Padding rows start at zero and a gate only mixes a row with
    partners inside its own padding block, so they stay zero."""
    return max(2**n, SUBLANES)


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` -> compiled Mosaic on TPU, the Pallas interpreter
    on the CPU backend, and an error anywhere else (a GPU or unknown
    backend would otherwise run the interpreter silently)."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"backend {backend!r} is neither"
    )


def _bit(x, q: int, n: int):
    """Row mask of x's shape: qubit q of the row's basis index is 1."""
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return (row & (1 << (n - 1 - q))) != 0


def _flip(x, q: int, n: int):
    """x with every row r replaced by row r ^ 2**(n-1-q) (qubit q flipped)."""
    stride = 1 << (n - 1 - q)
    down = pltpu.roll(x, stride, 0)  # row r <- row r - stride
    up = pltpu.roll(x, x.shape[0] - stride, 0)  # row r <- row r + stride
    return jnp.where(_bit(x, q, n), down, up)


def _rot1(re, im, q, n, c, s, kind):
    """Apply RX/RY/RZ with per-lane cos/sin (c, s) on qubit q."""
    if kind == "ry":  # [[c,-s],[s,c]] real
        sg = jnp.where(_bit(re, q, n), s, -s)
        return c * re + sg * _flip(re, q, n), c * im + sg * _flip(im, q, n)
    if kind == "rx":  # [[c,-is],[-is,c]]
        return c * re + s * _flip(im, q, n), c * im - s * _flip(re, q, n)
    if kind == "rz":  # diag(e^{-it/2}, e^{it/2})
        sg = jnp.where(_bit(re, q, n), -s, s)
        return c * re + sg * im, c * im - sg * re
    raise ValueError(kind)


def _rot2(re, im, qa, qb, n, c, s, kind):
    """RYY / RZZ / CRY / CRZ with per-lane (c, s); qa < qb required."""
    if kind == "rzz":  # e^{-it/2} on |00>,|11>; e^{+it/2} on |01>,|10>
        sg = jnp.where(_bit(re, qa, n) != _bit(re, qb, n), -s, s)
        return c * re + sg * im, c * im - sg * re
    if kind == "ryy":  # couples (00,11) with +i s, (01,10) with -i s
        sg = jnp.where(_bit(re, qa, n) != _bit(re, qb, n), s, -s)
        p_re = _flip(_flip(re, qa, n), qb, n)
        p_im = _flip(_flip(im, qa, n), qb, n)
        return c * re + sg * p_im, c * im - sg * p_re
    if kind in ("cry", "crz"):  # RY / RZ on qb within the qa=1 block
        nre, nim = _rot1(re, im, qb, n, c, s, kind[1:])
        ctrl = _bit(re, qa, n)
        return jnp.where(ctrl, nre, re), jnp.where(ctrl, nim, im)
    raise ValueError(kind)


def _h(re, im, q, n):
    inv = 0.7071067811865476
    b = _bit(re, q, n)
    return (
        (_flip(re, q, n) + jnp.where(b, -re, re)) * inv,
        (_flip(im, q, n) + jnp.where(b, -im, im)) * inv,
    )


def _cswap(re, im, qa, qb, qc_, n):
    """Fredkin: control qa, swap qb<->qc_ (qa < qb < qc_)."""
    sel = _bit(re, qa, n) & (_bit(re, qb, n) != _bit(re, qc_, n))
    return tuple(
        jnp.where(sel, _flip(_flip(x, qb, n), qc_, n), x) for x in (re, im)
    )


def _op_cos_sin(op, theta_ref, data_ref, delta: float = 0.0, invert: bool = False):
    """Per-lane (cos, sin) of half the op's angle (+ static shift delta,
    negated when inverting): (1, TB) rows read straight from the angle
    refs, or Python floats for constant angles."""
    kind, j = op.param
    sign = -1.0 if invert else 1.0  # rotation: g(t)^dagger = g(-t)
    if kind == "const":
        half = sign * (float(j) + delta) / 2
        return math.cos(half), math.sin(half)
    if kind == "theta":
        ang = theta_ref[j : j + 1, :]
    elif kind == "data":
        ang = data_ref[j : j + 1, :]
    else:
        raise ValueError(op.param)
    half = (ang + delta) * (sign / 2) if delta else ang * (sign / 2)
    return jnp.cos(half), jnp.sin(half)


def _apply_one(
    op, re, im, n, theta_ref, data_ref, delta: float = 0.0, invert: bool = False
):
    """Apply one gate (optionally angle-shifted by ``delta`` or inverted)."""
    if op.gate == "h":
        return _h(re, im, op.qubits[0], n)  # self-inverse
    if op.gate == "cswap":
        qa, qb, qc_ = op.qubits
        return _cswap(re, im, qa, qb, qc_, n)  # self-inverse
    if op.gate in ("rx", "ry", "rz"):
        c, s = _op_cos_sin(op, theta_ref, data_ref, delta, invert)
        return _rot1(re, im, op.qubits[0], n, c, s, op.gate)
    if op.gate in ("ryy", "rzz", "cry", "crz"):
        qa, qb = op.qubits
        if qa > qb:
            if op.gate in ("ryy", "rzz"):  # symmetric under qubit swap
                qa, qb = qb, qa
            else:
                raise NotImplementedError(
                    f"{op.gate} requires ascending (control, target) qubits"
                )
        c, s = _op_cos_sin(op, theta_ref, data_ref, delta, invert)
        return _rot2(re, im, qa, qb, n, c, s, op.gate)
    raise NotImplementedError(op.gate)


def _apply_ops(n: int, ops, re, im, theta_ref, data_ref):
    """Unrolled gate sequence on a (rows, TB) tile."""
    for op in ops:
        re, im = _apply_one(op, re, im, n, theta_ref, data_ref)
    return re, im


def _row_sum(x):
    """(rows, TB) -> (1, TB) column sums in a fixed association order:
    aligned halvings down to one vreg tile, then a rotate-and-add
    butterfly over its 8 sublanes.  A plain ``sum(axis=0)`` lets the CPU
    interpreter pick the order per launch shape, so the same lane could
    round differently when packed into a wider launch."""
    rows = x.shape[0]
    while rows > SUBLANES:
        rows //= 2
        x = x[:rows] + x[rows:]
    shift = SUBLANES // 2
    while shift:
        x = x + pltpu.roll(x, shift, 0)
        shift //= 2
    return x[0:1]


def _zero_tile(n: int, tb: int):
    """|0...0> batch of n-qubit states, built in VREGs — never read from HBM."""
    shape = (state_rows(n), tb)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    re = jnp.where(row == 0, 1.0, 0.0).astype(jnp.float32)
    return re, jnp.zeros(shape, jnp.float32)


# ------------------------------------------------------------------ kernels
def _fidelity_kernel(spec: CircuitSpec, theta_ref, data_ref, p0_ref):
    n = spec.n_qubits
    re, im = _zero_tile(n, theta_ref.shape[-1])
    re, im = _apply_ops(n, spec.ops, re, im, theta_ref, data_ref)
    prob = re * re + im * im
    half = jax.lax.broadcasted_iota(jnp.int32, prob.shape, 0) < (2**n // 2)
    p0_ref[...] = _row_sum(jnp.where(half, prob, 0.0))  # ancilla = MSB


def _state_kernel(spec: CircuitSpec, theta_ref, data_ref, re_ref, im_ref):
    n = spec.n_qubits
    re, im = _zero_tile(n, theta_ref.shape[-1])
    re_ref[...], im_ref[...] = _apply_ops(n, spec.ops, re, im, theta_ref, data_ref)


def _grid_call(
    spec: CircuitSpec, theta_t, data_t, tb: int, interpret: bool, want_state: bool
):
    """theta_t: (P, C), data_t: (D, C) with C % tb == 0."""
    p, c = theta_t.shape
    d = data_t.shape[0]
    rows = state_rows(spec.n_qubits)
    grid = (c // tb,)
    in_specs = [
        pl.BlockSpec((p, tb), lambda i: (0, i)),
        pl.BlockSpec((d, tb), lambda i: (0, i)),
    ]
    if want_state:
        out_shape = [jax.ShapeDtypeStruct((rows, c), jnp.float32)] * 2
        out_specs = [pl.BlockSpec((rows, tb), lambda i: (0, i))] * 2
        kern = functools.partial(_state_kernel, spec)
    else:
        out_shape = jax.ShapeDtypeStruct((1, c), jnp.float32)
        out_specs = pl.BlockSpec((1, tb), lambda i: (0, i))
        kern = functools.partial(_fidelity_kernel, spec)
    return pl.pallas_call(
        kern,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
    )(theta_t, data_t)


def _block_lanes(tb: int, interpret: bool) -> int:
    """Lane width of one grid block: ``tb`` compiled, LANES interpreted.
    XLA:CPU compiles the interpreted block body per block shape (fusion,
    FMA contraction), so a lane comes out bit-identical whether it is
    launched alone or packed with other banks only if every launch uses
    the same block width."""
    return LANES if interpret else tb


def _lane_major(x: jnp.ndarray, tb: int) -> jnp.ndarray:
    """(C, K) per-circuit angles -> (K, C + pad) lane-major f32, C padded
    up to a multiple of the lane tile."""
    pad = (-x.shape[0]) % tb
    return jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0))).T


def vqc_p0(
    spec: CircuitSpec,
    theta: jnp.ndarray,
    data: jnp.ndarray,
    tb: int = 4 * LANES,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Batched ancilla-P0 for a circuit bank. theta: (C,P), data: (C,D) -> (C,)."""
    c = theta.shape[0]
    interpret = resolve_interpret(interpret)
    tb = _block_lanes(kernel_tb(c, tb), interpret)
    p0 = _grid_call(
        spec,
        _lane_major(theta, tb),
        _lane_major(data, tb),
        tb,
        interpret,
        want_state=False,
    )
    return p0[0, :c]


def vqc_state(
    spec: CircuitSpec,
    theta: jnp.ndarray,
    data: jnp.ndarray,
    tb: int = LANES,
    interpret: bool | None = None,
):
    """Batched final statevector (re, im), each (C, 2**n) — for kernel tests."""
    c = theta.shape[0]
    interpret = resolve_interpret(interpret)
    tb = _block_lanes(kernel_tb(c, tb), interpret)
    re, im = _grid_call(
        spec,
        _lane_major(theta, tb),
        _lane_major(data, tb),
        tb,
        interpret,
        want_state=True,
    )
    dim = 2**spec.n_qubits
    return re[:dim, :c].T, im[:dim, :c].T


# ----------------------------------------------- shift-structured execution
#
# The parameter-shift circuit bank is pathologically redundant: its
# (1 + 2P) * B rows differ from the B base rows by exactly ONE angle each.
# ``vqc_p0`` on a materialized bank re-simulates every gate of every row —
# (1+2P) * G gate applications and (P+D) * (1+2P) angle floats per sample.
#
# The QuClassi circuit family has structure the kernel can verify statically
# and exploit to do far better than generic suffix replay:
#
#   * all ops before the SWAP-test tail act on two DISJOINT registers —
#     encoding on the data register (no trainable angles), the variational
#     stack on the trainable register (all trainable angles);
#   * the SWAP-test tail [H(a), CSWAP(a, d_i, t_i)..., H(a)] measures
#     P0 = (1 + |<psi_d|psi_t>|^2) / 2 exactly, so fidelity = 2*P0 - 1
#     = |<psi_d|psi_t>|^2 — an inner product of the two register states.
#
# The shift kernel therefore evolves the two 2**m-dim register states
# (m = register width) instead of the 2**(2m+1)-dim full state:
#
#   1. data register: ONE pass (theta-independent, shared by every variant);
#   2. trainable register FORWARD pass with base angles, checkpointing the
#      prefix state psi_k just before each parameter's FIRST dependent gate
#      in VMEM — 2*4*2**m*TB bytes per checkpointed prefix;
#   3. trainable register BACKWARD pass holding the reversed-suffix state
#      chi_k = (U_suffix_k)^dagger psi_d.  A single-use rotation gate's
#      shifted variant G_j(theta_j + s) then satisfies
#         F(j, s) = |<psi_d| U_suf G_j(theta_j+s) |psi_j>|^2
#                 = |<chi_j| G_j(theta_j+s) |psi_j>|^2,
#      i.e. ONE gate application plus one 2**m-dim inner product instead of
#      a full-circuit simulation.  A MULTI-USE parameter (positions
#      k_1 < ... < k_r) anchors at its LAST dependent gate: chi at k_r + 1
#      covers the unshifted remainder, and the variant REPLAYS only ops
#      k_1..k_r from the k_1 checkpoint with the shift added to each of its
#      own gates — replay depth k_r - k_1 + 1 gates per variant, still far
#      from the full-circuit resimulation the materialized bank pays.
#
# Per sample-tile the kernel reads (P + D) * TB angle floats (vs
# (P+D) * (1+2P) * TB materialized) and applies D_g + 2*T_g + sum(replay_j)
# register-local gates (vs (1+2P) * G full-state gates) — the ratios
# ``shift_bank_stats`` reports and benchmarks/kernel_bench.py tracks.
#
# Circuits that don't match the verified structure (interleaved registers,
# theta on the data register, non-rotation theta gates, non-SWAP-test tail)
# return ``None`` from ``build_shift_plan``.  Circuits WITH a plan whose
# suffix-replay cost exceeds the materialized bank's (a parameter reused
# across most of the circuit) are routed to the materialized path by the
# analytic ``shift_cost_info`` comparison in ``kernels.ops`` — the binary
# plan-exists decision became a cost crossover.

ROT_GATES = ("rx", "ry", "rz", "ryy", "rzz", "cry", "crz")


@dataclasses.dataclass(frozen=True)
class ShiftPlan:
    """Static execution plan for the prefix-reuse shift kernel.

    ``data_ops`` / ``train_ops`` are the body ops remapped to register-local
    qubit indices (register width ``m``); ``theta_positions[j]`` is the
    ascending tuple of indices into ``train_ops`` of parameter j's dependent
    gates — empty when the parameter drives no gate (its shifted fidelity is
    the base fidelity), length > 1 for multi-use parameters (executed by
    suffix replay over the [first, last] span).
    """

    m: int
    data_ops: tuple
    train_ops: tuple
    theta_positions: tuple[tuple[int, ...], ...]

    @property
    def theta_pos(self) -> tuple[int, ...]:
        """Legacy single-position view: parameter j's FIRST dependent gate
        (its checkpoint position), or -1 when it drives no gate."""
        return tuple(ps[0] if ps else -1 for ps in self.theta_positions)

    def replay_depth(self, j: int) -> int:
        """Gates a shift variant of parameter j replays from its checkpoint
        (1 for single-use parameters, 0 for unused ones)."""
        ps = self.theta_positions[j]
        return (ps[-1] - ps[0] + 1) if ps else 0


def _remap_op(op, mapping):
    return dataclasses.replace(op, qubits=tuple(mapping[q] for q in op.qubits))


@functools.lru_cache(maxsize=None)
def build_shift_plan(spec: CircuitSpec) -> ShiftPlan | None:
    """Verify the SWAP-test product structure; None -> caller must fall back."""
    ops = spec.ops
    # --- tail: H(anc), m CSWAP(anc, d_i, t_i), H(anc)
    if len(ops) < 3 or ops[-1].gate != "h":
        return None
    anc = ops[-1].qubits[0]
    k = len(ops) - 2
    pairs = []
    while k >= 0 and ops[k].gate == "cswap":
        a, d, t = ops[k].qubits
        if a != anc:
            return None
        pairs.append((d, t))
        k -= 1
    if k < 0 or ops[k].gate != "h" or ops[k].qubits != (anc,) or not pairs:
        return None
    pairs.reverse()
    data_q = [d for d, _ in pairs]
    train_q = [t for _, t in pairs]
    m = len(pairs)
    regs = set(data_q) | set(train_q) | {anc}
    if len(regs) != 2 * m + 1 or regs != set(range(spec.n_qubits)):
        return None
    data_map = {q: i for i, q in enumerate(data_q)}
    train_map = {q: i for i, q in enumerate(train_q)}

    # --- body: every op entirely inside one register; theta only on train
    data_ops, train_ops = [], []
    theta_pos: dict[int, list[int]] = {}
    for op in ops[:k]:
        qs = set(op.qubits)
        is_theta = op.param is not None and op.param[0] == "theta"
        if qs <= set(data_q):
            if is_theta or op.gate == "cswap":
                return None
            data_ops.append(_remap_op(op, data_map))
        elif qs <= set(train_q):
            if op.gate == "cswap":
                return None
            if is_theta:
                j = op.param[1]
                if op.gate not in ROT_GATES:
                    return None  # no shift rule for non-rotation theta gates
                # multi-use params accumulate their positions; the kernel
                # replays the [first, last] span per shift variant.
                theta_pos.setdefault(j, []).append(len(train_ops))
            train_ops.append(_remap_op(op, train_map))
        else:
            return None  # op straddles registers / touches ancilla
    # descending cry/crz would raise inside the kernel; reject here instead
    for op in data_ops + train_ops:
        if op.gate in ("cry", "crz") and op.qubits[0] > op.qubits[1]:
            return None
    pos = tuple(tuple(theta_pos.get(j, ())) for j in range(spec.n_theta))
    return ShiftPlan(
        m=m,
        data_ops=tuple(data_ops),
        train_ops=tuple(train_ops),
        theta_positions=pos,
    )


def _inner_fidelity(chi, phi):
    """|<chi|phi>|^2 per lane, (1, TB); chi/phi are (re, im) pairs of
    (rows, TB) — whole aligned tiles, padding rows included (all zero)."""
    cre, cim = chi
    pre, pim = phi
    ip_re = _row_sum(cre * pre + cim * pim)
    ip_im = _row_sum(cre * pim - cim * pre)
    return ip_re * ip_re + ip_im * ip_im


def _collect_variants(plan: ShiftPlan, shifts, groups, n_params: int):
    """Static (trace-time) map: ANCHOR train-op position -> [(group, param,
    shift)].

    A variant anchors at its parameter's LAST dependent gate — the backward
    pass's chi there covers the unshifted circuit remainder, and the shifted
    part replays forward from the checkpoint at the parameter's FIRST
    dependent gate (one gate for single-use parameters).  Position -1
    collects groups whose parameter drives no gate (their shifted fidelity
    is the base fidelity)."""
    wanted = set(groups)
    variants = {}
    for s_idx, s in enumerate(shifts):
        for j in range(n_params):
            g = 1 + s_idx * n_params + j
            if g not in wanted:
                continue
            ps = plan.theta_positions[j]
            variants.setdefault(ps[-1] if ps else -1, []).append((g, j, s))
    return variants


def _replay_variant(plan: ShiftPlan, j: int, s: float, state, theta_ref, data_ref):
    """Suffix replay for one shift variant: apply parameter j's dependent
    span of train ops to its checkpoint ``state``, the shift ``s`` added to
    every gate the parameter drives.  Single-use parameters degenerate to
    the one shifted gate application of the original kernel."""
    first, last = plan.theta_positions[j][0], plan.theta_positions[j][-1]
    re, im = state
    for k in range(first, last + 1):
        op = plan.train_ops[k]
        delta = s if op.param == ("theta", j) else 0.0
        re, im = _apply_one(op, re, im, plan.m, theta_ref, data_ref, delta=delta)
    return re, im


def _shiftbank_kernel(
    plan: ShiftPlan, shifts, groups, n_params: int, theta_ref, data_ref, out_ref
):
    """Compute the requested shift groups for one sample tile.

    Output rows follow ``groups``: group 0 is the base fidelity, group
    1 + s*P + j is shift s of param j (bank order).
    """
    tb = theta_ref.shape[-1]

    # 1. data register: one theta-independent pass, shared by every variant.
    d_re, d_im = _zero_tile(plan.m, tb)
    d_re, d_im = _apply_ops(plan.m, plan.data_ops, d_re, d_im, theta_ref, data_ref)

    wanted = set(groups)
    variants = _collect_variants(plan, shifts, groups, n_params)
    anchors = sorted(k for k in variants if k >= 0)
    firsts = {
        plan.theta_positions[j][0] for a in anchors for (_, j, _) in variants[a]
    }

    # 2. forward pass with base angles, checkpointing the prefix before each
    #    anchored parameter's FIRST dependent gate.
    checkpoints = {}
    t_re, t_im = _zero_tile(plan.m, tb)
    for k, op in enumerate(plan.train_ops):
        if k in firsts:
            checkpoints[k] = (t_re, t_im)
        t_re, t_im = _apply_one(op, t_re, t_im, plan.m, theta_ref, data_ref)

    rows = {}
    f0 = _inner_fidelity((d_re, d_im), (t_re, t_im))
    if 0 in wanted:
        rows[0] = f0
    for g, _, _ in variants.get(-1, ()):  # shifting an unused param is a no-op
        rows[g] = f0

    # 3. backward pass: chi = (suffix)^dagger psi_d; one suffix replay + one
    #    inner product per variant (a single gate for single-use params).
    #    chi below the shallowest anchor is never consumed — stop there.
    lowest = anchors[0] if anchors else len(plan.train_ops)
    c_re, c_im = d_re, d_im
    for k in range(len(plan.train_ops) - 1, lowest - 1, -1):
        op = plan.train_ops[k]
        for g, j, s in variants.get(k, ()):
            first = plan.theta_positions[j][0]
            v_re, v_im = _replay_variant(
                plan, j, s, checkpoints[first], theta_ref, data_ref
            )
            rows[g] = _inner_fidelity((c_re, c_im), (v_re, v_im))
        if k > lowest:
            c_re, c_im = _apply_one(
                op, c_re, c_im, plan.m, theta_ref, data_ref, invert=True
            )
    for i, g in enumerate(groups):  # one single-sublane store per row
        out_ref[i : i + 1, :] = rows[g]


# --------------------------------------- VMEM-aware checkpoint spilling
#
# The single-sweep kernel above holds EVERY needed prefix checkpoint live in
# VMEM between the forward and backward passes: P states of 2*4*2**m*TB
# bytes each.  For the paper's registers (m <= 3) that is kilobytes; for
# wide registers (m > 6 at the production TB = 512) the checkpoint set
# alone exceeds a TPU core's ~16 MB VMEM and the launch cannot lower.
# Rather than ejecting those circuits to the (1+2P)x-slower materialized
# path, the shift executor SPILLS: the train-op sequence is cut into depth
# tiles of at most ``cap`` checkpointed positions (a multi-use parameter's
# [first, last] replay span is atomic — tile boundaries never split it),
# the forward launch writes each tile's boundary prefix state to HBM (a
# pallas output), and ONE double-buffered backward launch sweeps every
# tile: each tile re-derives its <= cap checkpoints from the spilled
# boundary, consumes the reversed-suffix state chi carried over from the
# previous (deeper) tile in VMEM, and emits its variants' fidelity rows.
# The boundary fetches ping-pong between two VMEM buffers — tile t+1's
# async HBM copy is started before tile t's compute, so the fetch latency
# the old per-tile launches serialized now hides under gate application.
# Same op-application order per lane as the single sweep -> identical
# results; cost is one extra in-register forward pass (the recompute) plus
# 2 * (n_tiles + 1) register states of HBM spill traffic.

#: default per-launch checkpoint VMEM budget: ~16 MB/core minus headroom
#: for the angle blocks, the running states, and double buffering.  A
#: ``vmem_budget`` left None reads this when the plan is cut, not when the
#: module is imported.
VMEM_BUDGET_BYTES = 14 * 1024 * 1024

#: scoped VMEM each launch may use.  The budget above models the live
#: register states; gate application adds compiler temporaries (rotated
#: copies and row masks of the widest state) on top, which at m=8, TB=512
#: overflow the compiler's default 16 MiB scope.  A v5e core has 128 MiB.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)

#: live non-checkpoint states a tile launch holds (running state, chi,
#: boundary, one shifted variant) — reserved out of the budget.
_RESERVED_STATES = 4


def _state_bytes(m: int, tb: int) -> int:
    """Bytes of one (re, im) register state tile (sublane-padded rows)."""
    return 2 * 4 * state_rows(m) * tb


def checkpoint_vmem_bytes(plan: ShiftPlan, n_positions: int, tb: int) -> int:
    """VMEM the single-sweep kernel needs for its live checkpoint set."""
    return (n_positions + _RESERVED_STATES) * _state_bytes(plan.m, tb)


def _merge_spans(plan: ShiftPlan, positions):
    """Merge variant anchor positions into atomic (first, n_checkpoints)
    segments.

    Each anchor drags its parameter's whole [first, last] replay span along
    (single-use/point positions span themselves).  Overlapping spans fuse
    into one segment — a tile boundary inside a span would strand a replay's
    checkpoint in the previous tile.  Returns ascending (lo, n_ckpt) pairs
    where ``lo`` is the segment's first checkpoint position and ``n_ckpt``
    its distinct checkpoint count."""
    first_of = {ps[-1]: ps[0] for ps in plan.theta_positions if ps}
    segments: list[list] = []  # [lo, hi_anchor, {checkpoint positions}]
    for f, k in sorted((first_of.get(k, k), k) for k in positions):
        if segments and f <= segments[-1][1]:
            segments[-1][1] = max(segments[-1][1], k)
            segments[-1][2].add(f)
        else:
            segments.append([f, k, {f}])
    return [(seg[0], len(seg[2])) for seg in segments]


def plan_depth_tiles(
    plan: ShiftPlan, positions, tb: int, vmem_budget: int | None = None
):
    """Cut variant anchor positions into depth tiles that fit the budget.

    ``positions``: ascending train-op indices of variant anchors (a
    parameter's last dependent gate; equal to its checkpoint position for
    single-use parameters).  Returns None when every checkpoint fits in one
    sweep (no spilling), else a tuple of (lo, hi) train-op ranges — tile t
    re-derives its checkpoints from the spilled boundary state at op ``lo``
    and walks chi from op ``hi`` down to ``lo``.  Multi-use replay spans are
    atomic: a segment never straddles a tile boundary (an oversized segment
    becomes its own tile).  Single-use plans tile exactly as before.
    """
    positions = sorted(positions)
    if not positions:
        return None
    if vmem_budget is None:
        vmem_budget = VMEM_BUDGET_BYTES
    cap = max(1, vmem_budget // _state_bytes(plan.m, tb) - _RESERVED_STATES)
    segments = _merge_spans(plan, positions)
    if sum(n for _, n in segments) <= cap:
        return None
    chunks: list[list] = []
    cur, cur_n = [], 0
    for lo, n in segments:
        if cur and cur_n + n > cap:
            chunks.append(cur)
            cur, cur_n = [], 0
        cur.append((lo, n))
        cur_n += n
    if cur:
        chunks.append(cur)
    bounds = [c[0][0] for c in chunks] + [len(plan.train_ops)]
    return tuple(zip(bounds[:-1], bounds[1:]))


def plan_gate_apps(plan: ShiftPlan, shifts, groups, n_params: int) -> int:
    """Analytic per-lane gate applications of the prefix-reuse execution for
    the requested groups: the data-register pass + the forward pass + the
    backward inverse walk down to the shallowest anchor + every variant's
    suffix replay (one gate for single-use parameters, the [first, last]
    span for multi-use ones)."""
    variants = _collect_variants(plan, shifts, groups, n_params)
    anchors = [k for k in variants if k >= 0]
    total = len(plan.data_ops) + len(plan.train_ops)
    if not anchors:
        return total
    total += len(plan.train_ops) - min(anchors)
    for k in anchors:
        for _, j, _ in variants[k]:
            total += plan.replay_depth(j)
    return total


@functools.lru_cache(maxsize=None)
def shift_cost_info(
    spec: CircuitSpec,
    four_term: bool = False,
    groups: tuple[int, ...] | None = None,
) -> dict:
    """Analytic per-lane cost of executing a shift bank implicitly (prefix
    reuse + suffix replay) vs materialized ((1+2P)x full-circuit rows), and
    the mode the ops layer selects.  This replaces the old binary
    plan-exists -> fused decision: a plan whose replay cost exceeds the
    materialized bank's (a parameter reused across most of the circuit)
    routes to materialization.  The coalescer's ``batch_cost_units`` and
    ``api.backend.CostModel`` charge from the same numbers, so placement
    and admission see the true suffix-replay cost."""
    from repro.core.shift_rule import shift_values

    n_shifts = 4 if four_term else 2
    n_groups = 1 + n_shifts * spec.n_theta
    if groups is None:
        groups = tuple(range(n_groups))
    materialized = len(spec.ops) * len(groups)
    plan = build_shift_plan(spec)
    if plan is None:
        return {
            "gate_apps_implicit": None,
            "gate_apps_materialized": materialized,
            "replay_depth_max": 0,
            "use_implicit": False,
        }
    shifts = tuple(float(s) for s in shift_values(four_term))
    implicit = plan_gate_apps(plan, shifts, groups, spec.n_theta)
    depth = max((plan.replay_depth(j) for j in range(spec.n_theta)), default=0)
    return {
        "gate_apps_implicit": implicit,
        "gate_apps_materialized": materialized,
        "replay_depth_max": depth,
        "use_implicit": implicit < materialized,
    }


def use_shift_plan(
    spec: CircuitSpec,
    four_term: bool = False,
    groups: tuple[int, ...] | None = None,
) -> bool:
    """True when the implicit prefix-reuse path analytically beats
    materializing the requested groups (requires a plan to exist)."""
    return shift_cost_info(spec, four_term, groups)["use_implicit"]


def shift_execution_info(
    spec: CircuitSpec,
    n_samples: int,
    *,
    four_term: bool = False,
    groups: tuple[int, ...] | None = None,
    tb: int = 4 * LANES,
    vmem_budget: int | None = None,
) -> dict:
    """Static execution-mode report: which path a shift bank takes and what
    it costs.  ``mode`` is "materialize" (no product structure, or replay
    analytically dearer than materializing), "fused" (single-sweep
    prefix-reuse launch) or "spill" (VMEM-tiled prefix reuse; ``launches``
    counts the forward launch plus one per depth-tile segment of the
    double-buffered backward launch); the dispatcher's worker-VMEM model
    and the benchmarks both read this."""
    plan = build_shift_plan(spec)
    n_shifts = 4 if four_term else 2
    n_groups = 1 + n_shifts * spec.n_theta
    if groups is None:
        groups = tuple(range(n_groups))
    if vmem_budget is None:
        vmem_budget = VMEM_BUDGET_BYTES
    tb_eff = kernel_tb(n_samples, tb)
    cost = shift_cost_info(spec, four_term, tuple(groups))
    base = {
        "gate_apps_implicit": cost["gate_apps_implicit"],
        "gate_apps_materialized": cost["gate_apps_materialized"],
        "replay_depth_max": cost["replay_depth_max"],
        "vmem_budget": vmem_budget,
    }
    if plan is None or not cost["use_implicit"]:
        return {
            "mode": "materialize",
            "launches": 1,
            "n_tiles": 0,
            "vmem_bytes": _state_bytes(spec.n_qubits, tb_eff),
            **base,
        }
    from repro.core.shift_rule import shift_values

    variants = _collect_variants(plan, shift_values(four_term), groups, spec.n_theta)
    positions = sorted(k for k in variants if k >= 0)
    tiles = plan_depth_tiles(plan, positions, tb_eff, vmem_budget)
    if tiles is None:
        n_ckpt = len({plan.theta_positions[j][0] for k in positions
                      for (_, j, _) in variants[k]})
        return {
            "mode": "fused",
            "launches": 1,
            "n_tiles": 0,
            "vmem_bytes": checkpoint_vmem_bytes(plan, n_ckpt, tb_eff),
            **base,
        }
    # live checkpoints of the fullest tile; +1 state for the second
    # ping-pong boundary buffer of the double-buffered backward launch.
    # Tiling itself still budgets without the extra buffer (bit-identical
    # plan selection) — the 14 MB nominal budget already reserves the
    # double-buffering headroom below the ~16 MB physical VMEM.
    n_ckpt_max = max(
        len({plan.theta_positions[j][0] for k in positions if lo <= k < hi
             for (_, j, _) in variants[k]})
        for lo, hi in tiles
    )
    return {
        "mode": "spill",
        "launches": 1 + len(tiles),
        "n_tiles": len(tiles),
        "vmem_bytes": checkpoint_vmem_bytes(plan, n_ckpt_max, tb_eff)
        + _state_bytes(plan.m, tb_eff),
        "spill_buffer_bytes": _state_bytes(plan.m, tb_eff),
        "spilled_bytes": 2 * (len(tiles) + 1) * _state_bytes(plan.m, tb_eff),
        "overlap_ratio": round((len(tiles) - 1) / len(tiles), 4),
        **base,
    }


def _shift_forward_kernel(
    plan: ShiftPlan, tile_los, theta_ref, data_ref, f0_ref, d_ref, bnd_ref
):
    """Spill-mode forward launch: data-register pass, base fidelity, and the
    tile-boundary prefix states written to HBM (``bnd_ref`` rows are
    [re; im] stacks, one 2*rows block per tile)."""
    tb = theta_ref.shape[-1]
    rows = state_rows(plan.m)
    d_re, d_im = _zero_tile(plan.m, tb)
    d_re, d_im = _apply_ops(plan.m, plan.data_ops, d_re, d_im, theta_ref, data_ref)
    d_ref[:rows, :] = d_re
    d_ref[rows:, :] = d_im

    los = {lo: t for t, lo in enumerate(tile_los)}
    t_re, t_im = _zero_tile(plan.m, tb)
    for k, op in enumerate(plan.train_ops):
        if k in los:
            t = los[k]
            bnd_ref[2 * t * rows : (2 * t + 1) * rows, :] = t_re
            bnd_ref[(2 * t + 1) * rows : (2 * t + 2) * rows, :] = t_im
        t_re, t_im = _apply_one(op, t_re, t_im, plan.m, theta_ref, data_ref)
    f0_ref[...] = _inner_fidelity((d_re, d_im), (t_re, t_im))


def _shift_tile_kernel(
    plan: ShiftPlan,
    tile_plan,
    theta_ref,
    data_ref,
    chi_ref,
    bnd_hbm_ref,
    rows_ref,
    buf_a,
    buf_b,
    sems,
):
    """Double-buffered spill backward launch: EVERY depth tile in one call.

    ``tile_plan``: ((tile_index, lo, hi, rows_t), ...) deepest tile first,
    each rows_t a tuple of (group, param, shift, anchor) in descending
    anchor order; ``bnd_hbm_ref`` holds the forward launch's tile-boundary
    prefix states in HBM (memory space ANY, full array — sliced here by
    tile index and lane-grid position).  Two VMEM boundary buffers
    ping-pong: the async copy for the NEXT (shallower) tile's boundary is
    started before the current tile's compute, so the HBM fetch latency
    the old one-launch-per-tile path serialized now overlaps gate
    application.  chi is carried across tiles in registers (no HBM chi
    round-trip).  Per-lane op-application order is identical to the serial
    per-tile kernels — results are bit-identical."""
    tb = theta_ref.shape[-1]
    dim = state_rows(plan.m)
    i = pl.program_id(0)
    bufs = (buf_a, buf_b)

    def fetch(slot, pos):
        t = tile_plan[pos][0]
        return pltpu.make_async_copy(
            bnd_hbm_ref.at[pl.ds(2 * t * dim, 2 * dim), pl.ds(i * tb, tb)],
            bufs[slot],
            sems.at[slot],
        )

    fetch(0, 0).start()  # warm-up: the deepest tile's boundary
    c_re, c_im = chi_ref[:dim, :], chi_ref[dim:, :]
    out_rows = []
    for pos, (t, lo, hi, rows_t) in enumerate(tile_plan):
        slot = pos % 2
        if pos + 1 < len(tile_plan):
            fetch(1 - slot, pos + 1).start()  # next boundary in flight
        fetch(slot, pos).wait()
        # re-derive this tile's checkpoints from its boundary prefix state
        firsts = {plan.theta_positions[j][0] for (_, j, _, _) in rows_t}
        last = max(firsts)
        re, im = bufs[slot][:dim, :], bufs[slot][dim:, :]
        checkpoints = {}
        for k in range(lo, last + 1):
            if k in firsts:
                checkpoints[k] = (re, im)
            if k < last:
                re, im = _apply_one(
                    plan.train_ops[k], re, im, plan.m, theta_ref, data_ref
                )
        # chi walk + per-variant suffix replay, same order as the single
        # sweep; chi at lo seeds the next (shallower) tile directly.
        rows = {}
        for k in range(hi - 1, lo - 1, -1):
            op = plan.train_ops[k]
            for g, j, s, anchor in rows_t:
                if anchor != k:
                    continue
                first = plan.theta_positions[j][0]
                v = _replay_variant(
                    plan, j, s, checkpoints[first], theta_ref, data_ref
                )
                rows[g] = _inner_fidelity((c_re, c_im), v)
            if k > lo or pos + 1 < len(tile_plan):
                c_re, c_im = _apply_one(
                    op, c_re, c_im, plan.m, theta_ref, data_ref, invert=True
                )
        out_rows.extend(rows[g] for g, _, _, _ in rows_t)
    for r, row in enumerate(out_rows):  # one single-sublane store per row
        rows_ref[r : r + 1, :] = row


def _shift_fidelity_spilled(
    spec: CircuitSpec,
    plan: ShiftPlan,
    shifts,
    groups,
    tiles,
    theta_t,
    data_t,
    tb: int,
    interpret: bool,
) -> jnp.ndarray:
    """Orchestrate the spilled execution: one forward launch writes the
    tile-boundary prefix states to HBM, then ONE double-buffered backward
    launch sweeps every depth tile (``_shift_tile_kernel``), overlapping
    each tile's boundary fetch with the previous tile's compute.
    ``shift_execution_info``'s "launches" (1 + n_tiles) counts the forward
    launch plus the backward launch's per-tile segments — the unit the
    launch observer reports and the trend gate pins."""
    p, lanes = theta_t.shape
    d = data_t.shape[0]
    dim = state_rows(plan.m)
    n_tiles = len(tiles)
    grid = (lanes // tb,)
    lane_spec = lambda rows: pl.BlockSpec((rows, tb), lambda i: (0, i))  # noqa: E731
    in_specs = [lane_spec(p), lane_spec(d)]

    variants = _collect_variants(plan, shifts, groups, spec.n_theta)
    # both launches are named: a compiled program, and so the device trace,
    # names their Mosaic calls ``spill_forward`` and ``spill_tiles``; the
    # fused single-sweep launch keeps its jitted wrapper's name
    fwd = pl.pallas_call(
        functools.partial(_shift_forward_kernel, plan, tuple(lo for lo, _ in tiles)),
        name="spill_forward",
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        grid=grid,
        in_specs=in_specs,
        out_specs=[lane_spec(1), lane_spec(2 * dim), lane_spec(2 * n_tiles * dim)],
        out_shape=[
            jax.ShapeDtypeStruct((1, lanes), jnp.float32),
            jax.ShapeDtypeStruct((2 * dim, lanes), jnp.float32),
            jax.ShapeDtypeStruct((2 * n_tiles * dim, lanes), jnp.float32),
        ],
    )(theta_t, data_t)
    f0, d_state, boundaries = fwd

    rows_by_group = {}
    if 0 in groups:
        rows_by_group[0] = f0[0]
    for g, _, _ in variants.get(-1, ()):
        rows_by_group[g] = f0[0]

    tile_plan = []
    for t in range(n_tiles - 1, -1, -1):  # deepest tile first
        lo, hi = tiles[t]
        rows_t = tuple(
            (g, j, s, k)
            for k in range(hi - 1, lo - 1, -1)
            for (g, j, s) in variants.get(k, ())
        )
        tile_plan.append((t, lo, hi, rows_t))
    tile_plan = tuple(tile_plan)
    all_rows = tuple(r for (_, _, _, rows_t) in tile_plan for r in rows_t)

    rows_out = pl.pallas_call(
        functools.partial(_shift_tile_kernel, plan, tile_plan),
        name="spill_tiles",
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        grid=grid,
        in_specs=in_specs
        + [lane_spec(2 * dim), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=lane_spec(len(all_rows)),
        out_shape=jax.ShapeDtypeStruct((len(all_rows), lanes), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2 * dim, tb), jnp.float32),
            pltpu.VMEM((2 * dim, tb), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )(theta_t, data_t, d_state, boundaries)
    for idx, (g, _, _, _) in enumerate(all_rows):
        rows_by_group[g] = rows_out[idx]
    return jnp.stack([rows_by_group[g] for g in groups], axis=0)


def vqc_shift_fidelity(
    spec: CircuitSpec,
    theta: jnp.ndarray,
    data: jnp.ndarray,
    *,
    four_term: bool = False,
    groups: tuple[int, ...] | None = None,
    tb: int = 4 * LANES,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
) -> jnp.ndarray:
    """Prefix-reuse shift-bank fidelities. theta: (B,P), data: (B,D).

    Returns (G, B) where G = len(groups) (default: every group of the bank,
    1 + 2P or 1 + 4P rows) — row g is |<psi_d|psi_t>|^2 with the group's
    (param, shift) applied.  Flattening in group-major order reproduces the
    materialized bank's fidelity vector exactly (same layout).

    When the live checkpoint set exceeds ``vmem_budget`` (wide registers,
    m > 6 at production tile sizes) execution is automatically split into
    VMEM-sized depth tiles with boundary states spilled to HBM — same
    results, 1 + n_tiles launches instead of 1 (``shift_execution_info``
    reports the chosen mode).

    Raises ValueError when the spec doesn't match the SWAP-test product
    structure; call ``build_shift_plan`` first (or use ``kernels.ops``,
    which falls back to the materialized path).
    """
    plan = build_shift_plan(spec)
    if plan is None:
        raise ValueError(
            "circuit does not match the SWAP-test product "
            "structure; use the materialized-bank path"
        )
    interpret = resolve_interpret(interpret)
    n_shifts = 4 if four_term else 2
    n_groups = 1 + n_shifts * spec.n_theta
    if groups is None:
        groups = tuple(range(n_groups))
    if not groups or not all(0 <= g < n_groups for g in groups):
        raise ValueError(f"groups out of range for {n_groups}-group bank: {groups}")

    from repro.core.shift_rule import shift_values

    shifts = tuple(float(s) for s in shift_values(four_term))

    b = theta.shape[0]
    p, d = theta.shape[1], data.shape[1]
    tb = kernel_tb(b, tb)
    theta_t = _lane_major(theta, tb)
    data_t = _lane_major(data, tb)
    lanes = theta_t.shape[1]

    variants = _collect_variants(plan, shifts, groups, spec.n_theta)
    positions = sorted(k for k in variants if k >= 0)
    tiles = plan_depth_tiles(plan, positions, tb, vmem_budget)
    tb = _block_lanes(tb, interpret)
    if tiles is not None:
        out = _shift_fidelity_spilled(
            spec, plan, shifts, groups, tiles, theta_t, data_t, tb, interpret
        )
        return out[:, :b]

    g = len(groups)
    kern = functools.partial(_shiftbank_kernel, plan, shifts, groups, spec.n_theta)
    out = pl.pallas_call(
        kern,
        interpret=interpret,
        compiler_params=_COMPILER_PARAMS,
        grid=(lanes // tb,),
        in_specs=[
            pl.BlockSpec((p, tb), lambda i: (0, i)),
            pl.BlockSpec((d, tb), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((g, tb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((g, lanes), jnp.float32),
    )(theta_t, data_t)
    return out[:, :b]


# ------------------------------------------------------- analytic counters
def shift_bank_stats(
    spec: CircuitSpec, n_samples: int, four_term: bool = False
) -> dict:
    """Analytic gate-application and angle-traffic counts, implicit vs
    materialized — the ratios the acceptance benchmark tracks."""
    p, d = spec.n_theta, spec.n_data
    n_groups = 1 + (4 if four_term else 2) * p
    g_full = len(spec.ops)
    mat_gates = n_groups * g_full * n_samples
    mat_angle_floats = n_groups * n_samples * (p + d)
    cost = shift_cost_info(spec, four_term)
    if not cost["use_implicit"]:  # fallback executes the same work
        impl_gates = mat_gates
        impl_angle_floats = mat_angle_floats
    else:
        impl_gates = cost["gate_apps_implicit"] * n_samples
        impl_angle_floats = n_samples * (p + d)
    return {
        "n_groups": n_groups,
        "gate_apps_materialized": mat_gates,
        "gate_apps_implicit": impl_gates,
        "gate_apps_ratio": round(mat_gates / impl_gates, 1),
        "angle_bytes_materialized": 4 * mat_angle_floats,
        "angle_bytes_implicit": 4 * impl_angle_floats,
        "angle_bytes_ratio": round(mat_angle_floats / impl_angle_floats, 1),
    }


def multibank_stats(
    spec: CircuitSpec,
    bank_sizes,
    four_term: bool = False,
    vmem_budget: int | None = None,
) -> dict:
    """Analytic launch-count and lane accounting for a fused multi-bank
    shift execution of K same-spec banks vs K per-bank launches.

    ``bank_sizes``: per-bank sample counts B_k.  Per-bank execution costs
    one prefix-reuse launch per bank (times any spill tiling); the fused
    path packs every bank's LANES-padded lane segment into ONE launch and
    computes the union of the requested groups for all lanes.  Lane fill is
    identical by construction (per-bank segments pad independently in both
    paths); the fused win is the launch count — the metric the regression
    gate pins."""
    k = len(bank_sizes)
    occupied = sum(bank_sizes)
    padded = sum(-(-b // LANES) * LANES for b in bank_sizes)
    info = shift_execution_info(
        spec, max(bank_sizes), four_term=four_term, vmem_budget=vmem_budget
    )
    per_bank_launches = k * info["launches"]
    fused_info = shift_execution_info(
        spec, padded, four_term=four_term, vmem_budget=vmem_budget
    )
    fused_launches = fused_info["launches"]
    return {
        "n_banks": k,
        "bank_sizes": list(bank_sizes),
        "mode": fused_info["mode"],
        "launches_per_bank_path": per_bank_launches,
        "launches_fused": fused_launches,
        "launch_ratio": round(per_bank_launches / fused_launches, 2),
        "occupied_lanes": occupied,
        "padded_lanes": padded,
        "lane_fill": round(occupied / padded, 4),
    }
