"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to auto: real Mosaic lowering on TPU, interpret mode
on the CPU backend, an error on any other (``K.resolve_interpret``).  The wrappers are the executor used by the
co-Manager data plane and by ``shift_rule`` when kernel execution is on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.api.capabilities import declare
from repro.core.sim import CircuitSpec
from repro.kernels import vqc_statevector as K


@functools.partial(jax.jit, static_argnums=(0, 3))
def vqc_p0(
    spec: CircuitSpec,
    theta: jnp.ndarray,
    data: jnp.ndarray,
    tb: int = 4 * K.LANES,
) -> jnp.ndarray:
    return K.vqc_p0(spec, theta, data, tb=tb)


@functools.partial(jax.jit, static_argnums=(0,))
def vqc_fidelity(
    spec: CircuitSpec, theta: jnp.ndarray, data: jnp.ndarray
) -> jnp.ndarray:
    """Fused SWAP-test fidelity for a circuit bank: (C,P),(C,D) -> (C,)."""
    return jnp.clip(2.0 * K.vqc_p0(spec, theta, data) - 1.0, 0.0, 1.0)


@functools.partial(jax.jit, static_argnums=(0,))
def vqc_state(spec: CircuitSpec, theta: jnp.ndarray, data: jnp.ndarray):
    return K.vqc_state(spec, theta, data)


def kernel_executor(spec: CircuitSpec):
    """shift_rule.Executor backed by the fused Pallas kernel."""
    return lambda theta_bank, data_bank: vqc_fidelity(spec, theta_bank, data_bank)


# ----------------------------------------------- kernel profiling observer
#: module-level launch observer: when set, every shift-plan launch entering
#: through the public wrappers reports its static ``shift_execution_info``
#: (mode fused/spill/materialize, launches, tiles, VMEM footprint) plus the
#: lane/bank shape.  The hook lives OUTSIDE the jit boundary — the public
#: shift wrappers below are plain Python around inner jit'd functions — so
#: it fires once per launch, not once per trace.  None (default) costs one
#: global read per launch.
_launch_observer = None


def set_launch_observer(fn):
    """Install ``fn(info: dict)`` as the shift-launch observer (None
    disables).  Returns the previous observer so callers can restore it."""
    global _launch_observer
    prev = _launch_observer
    _launch_observer = fn
    return prev


def _notify_launch(spec, n_lanes, four_term, groups, banks=1):
    obs = _launch_observer
    if obs is None:
        return
    info = dict(
        K.shift_execution_info(spec, n_lanes, four_term=four_term, groups=groups)
    )
    info["lanes"] = n_lanes
    info["banks"] = banks
    obs(info)
    if info["mode"] != "spill":
        return
    # spill path: one event per depth-tile launch segment (the summary event
    # above covers the forward launch), so traces show the double-buffered
    # backward sweep — tile order, which ping-pong boundary buffer each tile
    # fetches into, and whether that fetch overlapped the previous tile's
    # compute.  Total events = info["launches"].
    n_tiles = info["n_tiles"]
    for order in range(n_tiles):
        obs(
            {
                "mode": "spill_tile",
                "tile": n_tiles - 1 - order,  # tiles run deepest-first
                "tile_order": order,
                "buffer": order % 2,
                "boundary_bytes": info["spill_buffer_bytes"],
                "overlapped": order > 0,
                "lanes": n_lanes,
                "banks": banks,
            }
        )


# ------------------------------------------------- shift-structured banks
@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _shiftgroups_jit(
    spec: CircuitSpec,
    theta: jnp.ndarray,
    data: jnp.ndarray,
    four_term: bool = False,
    groups: tuple[int, ...] | None = None,
) -> jnp.ndarray:
    from repro.core import shift_rule

    if K.use_shift_plan(spec, four_term, groups):
        return jnp.clip(
            K.vqc_shift_fidelity(spec, theta, data, four_term=four_term, groups=groups),
            0.0,
            1.0,
        )
    descs = shift_rule.group_descriptors(theta.shape[1], four_term)
    if groups is None:
        groups = tuple(range(len(descs)))
    blocks = []
    for g in groups:
        j, s = descs[g]
        blocks.append(theta if j < 0 else theta.at[:, j].add(s))
    b = theta.shape[0]
    theta_bank = jnp.concatenate(blocks, 0)
    data_bank = jnp.tile(data, (len(groups), 1))
    return vqc_fidelity(spec, theta_bank, data_bank).reshape(len(groups), b)


def vqc_fidelity_shiftgroups(
    spec: CircuitSpec,
    theta: jnp.ndarray,
    data: jnp.ndarray,
    four_term: bool = False,
    groups: tuple[int, ...] | None = None,
) -> jnp.ndarray:
    """Shift-bank fidelities for the requested groups, (G, B).

    ``theta (B, P)`` / ``data (B, D)`` are the IMPLICIT bank — base angles
    only.  Uses the prefix-reuse kernel when the circuit matches the
    SWAP-test product structure AND the analytic suffix-replay cost beats
    materializing the requested groups (``K.shift_cost_info`` — multi-use
    parameters replay their dependent span per variant, so deep reuse with
    a small group request can flip the decision); spills prefix checkpoints
    to HBM in depth tiles when the register is too wide for VMEM.
    Otherwise materializes just the requested groups and runs the standard
    fused kernel (same results, more work).
    """
    _notify_launch(spec, theta.shape[0], four_term, groups)
    return _shiftgroups_jit(spec, theta, data, four_term, groups)


def vqc_fidelity_shiftbank(
    spec: CircuitSpec,
    theta: jnp.ndarray,
    data: jnp.ndarray,
    four_term: bool = False,
) -> jnp.ndarray:
    """Whole implicit bank -> flat (C,) fidelities in materialized-bank order."""
    return vqc_fidelity_shiftgroups(spec, theta, data, four_term).reshape(-1)


def _pack_banks(thetas, datas):
    """Pad each bank's samples to a LANES multiple and concatenate along the
    lane axis.  Returns (theta_cat, data_cat, segments) with ``segments[k] =
    (lane_offset, n_samples_k)`` — static Python ints, so downstream slicing
    stays trace-free."""
    t_parts, d_parts, segments = [], [], []
    off = 0
    for t, d in zip(thetas, datas):
        b = t.shape[0]
        pad = (-b) % K.LANES
        t_parts.append(jnp.pad(t.astype(jnp.float32), ((0, pad), (0, 0))))
        d_parts.append(jnp.pad(d.astype(jnp.float32), ((0, pad), (0, 0))))
        segments.append((off, b))
        off += b + pad
    return (
        jnp.concatenate(t_parts, 0),
        jnp.concatenate(d_parts, 0),
        tuple(segments),
    )


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _shiftgroups_multibank_jit(
    spec: CircuitSpec, thetas, datas, four_term: bool, group_sets: tuple
) -> tuple:
    union = tuple(sorted({g for gs in group_sets for g in gs}))
    if not K.use_shift_plan(spec, four_term, union):
        return tuple(
            _shiftgroups_jit(spec, t, d, four_term, gs)
            for t, d, gs in zip(thetas, datas, group_sets)
        )
    theta_cat, data_cat, segments = _pack_banks(thetas, datas)
    out = jnp.clip(
        K.vqc_shift_fidelity(
            spec, theta_cat, data_cat, four_term=four_term, groups=union
        ),
        0.0,
        1.0,
    )
    row = {g: i for i, g in enumerate(union)}
    return tuple(
        jnp.stack([out[row[g], off : off + b] for g in gs], axis=0)
        for (off, b), gs in zip(segments, group_sets)
    )


def vqc_fidelity_shiftgroups_multibank(
    spec: CircuitSpec, thetas, datas, four_term: bool, group_sets: tuple
) -> tuple:
    """FUSED multi-bank shift execution: K same-spec implicit banks in ONE
    prefix-reuse kernel launch.

    ``thetas`` / ``datas``: tuples of K per-bank base-angle arrays
    ((B_k, P), (B_k, D)); ``group_sets[k]``: the (param, shift) groups
    requested for bank k.  Each bank occupies its own LANES-padded lane
    segment of the launch; base angles are per-lane, so different banks
    (different thetas, even different sample counts) share the one
    data-register pass, checkpointed forward pass, and reversed-suffix
    backward pass — K x (1+2P) per-bank launches collapse to the union
    group set in ONE launch.  Returns a tuple of (len(group_sets[k]), B_k)
    fidelity blocks, each bit-identical per lane to the per-bank path.

    Circuits without the verified product structure — or whose suffix-replay
    cost for the union group set exceeds materializing it — fall back to
    per-bank materialized execution (correct, not fused).
    """
    if _launch_observer is not None:
        union = tuple(sorted({g for gs in group_sets for g in gs}))
        lanes = sum(t.shape[0] + (-t.shape[0]) % K.LANES for t in thetas)
        _notify_launch(spec, lanes, four_term, union, banks=len(thetas))
    return _shiftgroups_multibank_jit(spec, thetas, datas, four_term, group_sets)


def multibank_executor(spec: CircuitSpec):
    """A bank-set executor (declared ``multibank`` capability): runs a
    sequence of same-spec ``ShiftBank``s as one fused multi-bank launch and
    returns the per-bank flat fidelity vectors in bank order."""

    def run(banks):
        four = {b.four_term for b in banks}
        if len(four) > 1:
            raise ValueError("banks in one fused set must share four_term")
        outs = vqc_fidelity_shiftgroups_multibank(
            spec,
            tuple(b.theta for b in banks),
            tuple(b.data for b in banks),
            four.pop(),
            tuple(tuple(range(b.n_groups)) for b in banks),
        )
        return [o.reshape(-1) for o in outs]

    return declare(run, multibank=True)


def shiftbank_executor(spec: CircuitSpec):
    """A ``shift_rule.Executor`` that consumes implicit ``ShiftBank``s
    directly (declared ``shiftbank`` capability) via the prefix-reuse
    kernel.  Also accepts plain ``(theta_bank, data_bank)`` calls —
    materialized banks run through the standard fused kernel, so the
    executor composes with every bank mode."""

    def run(bank, data_bank=None):
        if data_bank is not None:
            return vqc_fidelity(spec, bank, data_bank)
        return vqc_fidelity_shiftbank(spec, bank.theta, bank.data, bank.four_term)

    return declare(run, shiftbank=True)
