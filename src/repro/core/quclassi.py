"""The DQuLearn training workload: a quantum-classical CNN classifier
(QuClassi [29] as used by the paper, Algorithm 1).

Pipeline per image:
  Task Segmentation -> patches (B, Np, w*w)
  classical dense layer -> data-encoding angles per patch (Algorithm 1 l.10)
  per class c: SWAP-test fidelity F_c(patch) against trainable register theta_c
  class score = mean over patches of F_c; one-vs-all BCE loss.

Two gradient paths:
  * ``grad_shift``    — the paper's distributed path: parameter-shift circuit
    bank per class, executable by any ``Executor`` (locally, or routed
    through the co-Manager to quantum workers).
  * ``grad_autodiff`` — exact gradients through the simulator; used as the
    fast local path and the correctness oracle (identical for single/dual
    layers where the 2-term rule is exact).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.api.capabilities import capabilities_of
from repro.core import circuits, fidelity as fid, segmentation, shift_rule
from repro.core.sim import CircuitSpec
from repro.obs import span


@dataclasses.dataclass(frozen=True)
class QuClassiConfig:
    qc: int = 5                   # qubit count (paper: 5 or 7)
    n_layers: int = 1             # 1..3 (single / +dual / +entangle)
    n_classes: int = 2
    seg: segmentation.SegmentationConfig = segmentation.SegmentationConfig()
    image_size: tuple[int, int] = (8, 8)   # paper downsamples MNIST patches
    use_dense: bool = True

    @property
    def spec(self) -> CircuitSpec:
        return circuits.build_quclassi_circuit(self.qc, self.n_layers)

    @property
    def n_theta(self) -> int:
        return circuits.n_theta_for(self.qc, self.n_layers)

    @property
    def n_angles(self) -> int:
        return circuits.n_data_angles_for(self.qc)

    @property
    def patch_dim(self) -> int:
        return self.seg.filter_width**2

    @property
    def n_patches(self) -> int:
        ph, pw = segmentation.n_patches(*self.image_size, self.seg)
        return ph * pw


def init_params(cfg: QuClassiConfig, key: jax.Array) -> dict:
    """Network weights: theta ~ U[0, pi] per class (Algorithm 1 l.2)."""
    k1, k2 = jax.random.split(key)
    params = {
        "theta": jax.random.uniform(
            k1, (cfg.n_classes, cfg.n_theta), minval=0.0, maxval=jnp.pi
        ),
    }
    if cfg.use_dense:
        scale = 1.0 / jnp.sqrt(cfg.patch_dim)
        params["w"] = jax.random.normal(k2, (cfg.patch_dim, cfg.n_angles)) * scale
        params["b"] = jnp.zeros((cfg.n_angles,))
    return params


def encode_patches(
    cfg: QuClassiConfig, params: dict, patches: jnp.ndarray
) -> jnp.ndarray:
    """(B, Np, w*w) patches -> (B, Np, n_angles) rotation angles."""
    if cfg.use_dense:
        z = patches @ params["w"] + params["b"]            # dense layer (l.10-11)
        return jnp.pi * jax.nn.sigmoid(z)
    from repro.core import encoding
    return encoding.rotation_angles(patches, cfg.n_angles)


def class_fidelities(
    cfg: QuClassiConfig, params: dict, images: jnp.ndarray
) -> jnp.ndarray:
    """(B, H, W) images -> (B, n_classes) mean patch fidelity per class.

    F is the overlap of the two registers the SWAP test compares
    (``fidelity.register_fidelities``): one 2**m-amplitude data-register
    state per patch and one trainable-register state per class, never a
    2**qc-amplitude state."""
    patches = segmentation.segment(images, cfg.seg)        # (B, Np, P)
    angles = encode_patches(cfg, params, patches)          # (B, Np, A)
    flat = angles.reshape(-1, angles.shape[-1])            # (B*Np, A)
    regs = fid.register_specs(cfg.spec)
    f = fid.register_fidelities(regs, params["theta"], flat)  # (B*Np, C)
    return f.reshape(angles.shape[0], -1, f.shape[-1]).mean(1)


def one_vs_all_loss(fids: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """fids (B, C), integer labels (B,) -> scalar mean BCE over classes."""
    onehot = jax.nn.one_hot(labels, fids.shape[-1])
    return fid.bce_loss(fids, onehot).mean()


def predict(cfg: QuClassiConfig, params: dict, images: jnp.ndarray) -> jnp.ndarray:
    return class_fidelities(cfg, params, images).argmax(-1)


@functools.partial(jax.jit, static_argnums=0)
def accuracy(cfg: QuClassiConfig, params: dict, images, labels) -> jnp.ndarray:
    """Share of ``images`` whose predicted class is ``labels``: one compiled
    program per (config, shapes)."""
    return (predict(cfg, params, images) == labels).mean()


# ------------------------------------------------------------ gradient paths
def grad_autodiff(cfg: QuClassiConfig, params: dict, images, labels):
    """Exact gradients for all parameters (dense + quantum) via the simulator."""
    def loss_fn(p):
        f = class_fidelities(cfg, p, images)
        return one_vs_all_loss(f, labels), f
    (loss, f), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return loss, g, f


@functools.partial(jax.jit, static_argnums=0)
def _dense_grad(cfg: QuClassiConfig, w, b, theta, images, labels) -> dict:
    """Exact dense-layer gradient ``{"w", "b"}`` of the one-vs-all loss,
    holding ``theta`` fixed: one compiled program per (config, shapes)."""
    def dense_loss(wb):
        f = class_fidelities(cfg, dict(wb, theta=theta), images)
        return one_vs_all_loss(f, labels)
    return jax.grad(dense_loss)({"w": w, "b": b})


def build_class_banks(
    cfg: QuClassiConfig, params: dict, images: jnp.ndarray, implicit: bool = False
):
    """The distributable work unit: one circuit bank per class (Algorithm 1).

    Returns (banks, angles) where banks[c] covers every (patch, shifted-theta)
    circuit for class c.  Total circuits = C * (B*Np) * (2*P + 1).

    ``implicit=True`` builds ``ShiftBank``s — base angles + shift descriptors
    only, never the (C, P) theta matrix; ``shiftbank``-capable executors run
    them with the prefix-reuse kernel, everything else via ``materialize()``.
    """
    patches = segmentation.segment(images, cfg.seg)
    angles = encode_patches(cfg, params, patches).reshape(-1, cfg.n_angles)
    build = shift_rule.build_shift_bank if implicit else shift_rule.build_bank
    banks = [build(params["theta"][c], angles) for c in range(cfg.n_classes)]
    return banks, angles


def grad_shift(
    cfg: QuClassiConfig,
    params: dict,
    images,
    labels,
    executor: shift_rule.Executor | None = None,
    implicit: bool | None = None,
):
    """Paper-faithful distributed gradient: execute per-class circuit banks
    (optionally through the co-Manager) and assemble theta gradients.

    ``implicit``: route through implicit ``ShiftBank``s (None = auto: exactly
    when the executor declares the ``shiftbank`` capability).

    Dense-layer params, when present, are trained with exact chain-rule
    gradients holding theta fixed (autodiff through the data-encoding path) —
    see DESIGN.md §2 for why this mirrors the paper's classical update.  That
    gradient is one compiled program (``_dense_grad``) per config and input
    shapes.
    """
    spec = cfg.spec
    run = executor or shift_rule.default_executor(spec)
    if implicit is None:
        implicit = capabilities_of(run).shiftbank
    banks, _ = build_class_banks(cfg, params, images, implicit=implicit)
    onehot = jax.nn.one_hot(labels, cfg.n_classes)
    b, np_ = images.shape[0], cfg.n_patches

    theta_grads, losses, fids_per_class = [], [], []
    for c, bank in enumerate(banks):
        with span("trainer.bank"):
            fids = shift_rule.run_bank(run, bank)
        f0, f_plus, f_minus = bank.split_results(fids)[:3]
        # class score per image = mean patch fidelity (matches
        # class_fidelities); chain BCE through the per-image MEAN, then
        # distribute to the per-patch shift-rule estimates.
        f_img = f0.reshape(b, np_).mean(-1)                       # (B,)
        dfdt = (f_plus - f_minus) / 2.0                           # (P, B*Np)
        df_img = dfdt.reshape(-1, b, np_).mean(-1)                # (P, B)
        chain = fid.bce_grad_wrt_fidelity(f_img, onehot[:, c])    # (B,)
        # 1/(B*C) normalization to match one_vs_all_loss's mean over (B, C)
        theta_grads.append((df_img * chain[None, :]).mean(-1) / cfg.n_classes)
        losses.append(fid.bce_loss(f_img, onehot[:, c]).mean())
        fids_per_class.append(f_img)

    grads = {"theta": jnp.stack(theta_grads)}
    if cfg.use_dense:
        with span("trainer.dense_grad"):
            grads.update(_dense_grad(
                cfg, params["w"], params["b"], params["theta"], images, labels
            ))
    loss = jnp.stack(losses).mean()
    return loss, grads, jnp.stack(fids_per_class, -1)


def total_bank_circuits(cfg: QuClassiConfig, batch: int) -> int:
    """Circuits per gradient step — the workload the co-Manager schedules."""
    per_class = batch * cfg.n_patches * (2 * cfg.n_theta + 1)
    return cfg.n_classes * per_class
