"""DQuLearn training driver — Algorithm 1's epoch loop, end to end.

Per epoch (lines 4-26): start timer -> segment data / encode -> build the
parameter-shift circuit bank -> execute every circuit in the bank through the
chosen executor (local fused kernel, per-worker batches, or a sharded mesh)
-> assemble gradients -> update parameters -> stop timer, record accuracy.

The classical half runs as compiled programs: the dense layer's gradient
(inside ``quclassi.grad_shift``) and each accuracy pass
(``quclassi.accuracy``) are one jitted call each, keyed on the static
``QuClassiConfig`` and the input shapes.  ``pipeline.batches`` drops the
remainder, so every step of a run has the same shapes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quclassi
from repro.core.quclassi import QuClassiConfig
from repro.data import pipeline
from repro.obs import span
from repro.optim import optimizers


@dataclasses.dataclass
class EpochRecord:
    epoch: int
    loss: float
    train_accuracy: float
    test_accuracy: float
    wall_seconds: float
    circuits_executed: int


@dataclasses.dataclass
class TrainReport:
    epochs: list[EpochRecord]
    params: dict

    @property
    def final_test_accuracy(self) -> float:
        return self.epochs[-1].test_accuracy if self.epochs else 0.0


def train(
    cfg: QuClassiConfig,
    train_set,
    test_set,
    *,
    epochs: int = 10,
    batch_size: int = 8,
    lr: float = 1e-3,
    grad_mode: str = "shift",
    executor=None,
    optimizer: str = "sgd",
    gateway=None,
    client_id: str = "trainer",
    bank_mode: str = "auto",
    priority: int = 1,
    slo_ms: Optional[float] = None,
    policy=None,
    seed: int = 0,
    log: Optional[Callable[[str], None]] = None,
) -> TrainReport:
    """Train QuClassi per Algorithm 1.

    ``grad_mode``: 'shift' (paper-faithful circuit-bank path, optionally
    distributed via ``executor``) or 'autodiff' (exact local path — same
    math for 1-2 layer configs, used for fast accuracy runs).

    ``gateway``: a ``repro.serve.GatewayRuntime``; the shift-rule circuit
    banks are then streamed through the online serving gateway as client
    ``client_id`` — coalesced (possibly with other tenants sharing the
    runtime) into lane-aligned mega-batches, placed by the co-Manager, and
    executed by the fused Pallas kernel.  Fidelities come back in submission
    order, so gradient assembly is unchanged.  A runtime constructed with
    ``mode="async"`` rides the async path transparently: submissions stream
    into the pump loop while earlier batches execute on the worker pool, and
    the per-bank gather blocks on out-of-order futures.

    ``priority`` / ``slo_ms`` (gateway mode): this client's strict
    scheduling tier (lower = served first — a tier-0 interactive tenant
    preempts tier-1 training traffic) and end-to-end latency SLO, forwarded
    to ``Gateway.register_client``.

    ``bank_mode``: 'materialized' (explicit (C, P) circuit banks),
    'implicit' (``ShiftBank``s — shift-aware executors run them through the
    prefix-reuse kernel; a gateway then carries per-(param, shift) group
    subtasks instead of per-row circuits), or 'auto' (implicit exactly when
    the executor declares the ``shiftbank`` capability — see
    ``repro.api.capabilities``).

    ``policy``: a ``repro.api.TenantPolicy``; when given it supersedes the
    loose ``priority`` / ``slo_ms`` kwargs (the preferred way to carry a
    tenant's scheduling contract — ``repro.api.Session.train`` wires it).
    """
    if bank_mode not in ("auto", "implicit", "materialized"):
        raise ValueError(f"unknown bank_mode {bank_mode!r}")
    if policy is not None:
        priority, slo_ms = policy.priority, policy.slo_ms
    implicit = {"auto": None, "implicit": True, "materialized": False}[bank_mode]
    if gateway is not None:
        if executor is not None:
            raise ValueError("pass either executor or gateway, not both")
        gw_opts = dict(priority=priority, slo_ms=slo_ms)
        if policy is not None:
            gw_opts["weight"] = policy.weight
        executor = (
            gateway.shift_executor(cfg.spec, client_id, **gw_opts)
            if bank_mode == "implicit"
            else gateway.executor(cfg.spec, client_id, **gw_opts)
        )
    (xtr, ytr), (xte, yte) = train_set, test_set
    xtr, xte = pipeline.clean(xtr), pipeline.clean(xte)
    # program spans record into the serving runtime's trace recorder; the
    # spans grad_shift opens inherit it
    recorder = gateway.telemetry.trace if gateway is not None else None
    params = quclassi.init_params(cfg, jax.random.PRNGKey(seed))
    opt = optimizers.make(optimizer, lr)
    opt_state = opt.init(params)
    records: list[EpochRecord] = []

    for epoch in range(epochs):                       # line 4
        t0 = time.perf_counter()                      # line 5: epoch timer
        losses, n_circ = [], 0
        for bi, (xb, yb) in enumerate(
            pipeline.batches(xtr, ytr, batch_size, seed=seed * 997 + epoch)
        ):
            with span("trainer.step", recorder):
                xb, yb = jnp.asarray(xb), jnp.asarray(yb)
                if grad_mode == "shift":
                    loss, grads, _ = quclassi.grad_shift(
                        cfg, params, xb, yb, executor=executor, implicit=implicit
                    )
                    n_circ += quclassi.total_bank_circuits(cfg, xb.shape[0])
                else:
                    loss, grads, _ = quclassi.grad_autodiff(cfg, params, xb, yb)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optimizers.apply_updates(params, updates)
                losses.append(float(loss))
        wall = time.perf_counter() - t0               # lines 24-25
        with span("trainer.accuracy", recorder):
            tr_acc = float(
                quclassi.accuracy(cfg, params, jnp.asarray(xtr), jnp.asarray(ytr))
            )
        with span("trainer.accuracy", recorder):
            te_acc = float(
                quclassi.accuracy(cfg, params, jnp.asarray(xte), jnp.asarray(yte))
            )
        rec = EpochRecord(epoch, float(np.mean(losses)), tr_acc, te_acc, wall, n_circ)
        records.append(rec)                           # line 26: accuracy/epoch
        if log:
            log(
                f"epoch {epoch}: loss={rec.loss:.4f} train_acc={tr_acc:.3f} "
                f"test_acc={te_acc:.3f} wall={wall:.2f}s circuits={n_circ}"
            )
    return TrainReport(records, params)
