"""Dispatcher: coalesced mega-batches -> co-Manager placement -> Pallas kernel.

One ``CoalescedBatch`` becomes ONE logical circuit-bank task for Algorithm 2:
its resource demand is the spec's qubit width (the co-resident lanes of a
fused kernel batch occupy one ``n_qubits``-wide register file slot on the
worker, not ``n * width`` qubits), so the existing capacity/CRU assignment
logic routes whole batches exactly as it routed single circuits.

Cost model: every batch carries an analytic work estimate
(``batch_cost_units`` — gate applications x padded kernel lanes; for
shift-group subtasks the TRUE prefix-reuse cost, including the suffix depth
the backward pass must cover) which the ``Telemetry.service`` EWMA converts
into predicted seconds.  The prediction becomes the task's ``service_time``
AND is charged to the assigned worker's CRU while the batch is outstanding,
so Algorithm 2's lowest-CRU-first choice routes new batches toward the
worker with the least predicted backlog.

This module holds the *dispatch core* — placement (``Dispatcher._place``),
execute-and-settle on a worker (``_execute``) and the whole-mesh path
(``_execute_spilled``) — and its synchronous driver, which runs each batch
inline on the submitting thread.  The non-blocking driver of the same core,
with a pump loop and per-worker execution slots, is
``repro.serve.async_dispatcher.AsyncDispatcher``; the virtual-clock
counterpart lives in ``repro.comanager.simulation`` (``gateway=True``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.capabilities import declare
from repro.comanager.faults import FaultToleranceConfig
from repro.comanager.manager import CoManager
from repro.comanager.tenancy import TaskIdAllocator
from repro.comanager.worker import CircuitTask, WorkerConfig
from repro.core.sim import CircuitSpec
from repro.kernels import ops as kops
from repro.kernels import vqc_statevector as kvs
from repro.kernels.vqc_statevector import (
    LANES,
    kernel_tb,
    shift_cost_info,
    shift_execution_info,
)
from repro.obs import span
from repro.serve.coalescer import CoalescedBatch
from repro.serve.fleet import FaultInjector, FleetHealth
from repro.serve.gateway import Backpressure, Gateway
from repro.serve.metrics import Telemetry

#: kernel runner signature: (spec, theta (C,P), data (C,D)) -> fidelities (C,)
KernelFn = Callable[[CircuitSpec, jnp.ndarray, jnp.ndarray], jnp.ndarray]

#: shift-group runner: (spec, theta (B,P), data (B,D), four_term, groups)
#: -> per-group fidelities (len(groups), B)
ShiftKernelFn = Callable[
    [CircuitSpec, jnp.ndarray, jnp.ndarray, bool, tuple], jnp.ndarray
]

#: fused multi-bank runner: (spec, thetas, datas, four_term, group_sets)
#: -> per-bank (len(group_sets[k]), B_k) fidelity blocks
MultiBankKernelFn = Callable[[CircuitSpec, tuple, tuple, bool, tuple], tuple]


@dataclasses.dataclass(frozen=True)
class ShiftGroupKey:
    """Coalescing key for implicit-bank (param, shift) group subtasks.

    Keyed by circuit STRUCTURE only: group subtasks of *different* banks —
    different tenants, different base angles, different sample counts — of
    the same ``CircuitSpec`` and shift rule share a key and coalesce into
    joint multi-bank prefix-reuse launches (base angles are per-lane data
    of the fused kernel, so they never had to keep banks apart)."""

    spec: CircuitSpec
    four_term: bool = False


# --------------------------------------------------------- shared execution
def batch_spec(batch: CoalescedBatch) -> CircuitSpec:
    key = batch.key
    if isinstance(key, CircuitSpec):
        return key
    if isinstance(key, ShiftGroupKey):
        return key.spec
    raise TypeError(
        f"dispatcher batches must be keyed by CircuitSpec or "
        f"ShiftGroupKey, got {type(key).__name__}"
    )


def bank_partition(batch: CoalescedBatch):
    """Split a shift-group batch's members into per-bank subtask lists.

    Returns ``(banks, group_sets, slots)``: the distinct ``ShiftBank``s in
    first-appearance order, each bank's whole group tuple, and for every
    member its ``(bank_index, row_index)`` into the fused kernel's per-bank
    output blocks.  A launch computes every group of each of its banks,
    whichever of them the batch carries: which groups a batch carries
    depends on how the coalescer split concurrent banks between batches,
    and a kernel program keyed on that split would compile anew for each
    split it meets."""
    banks, slots = [], []
    index: dict[int, int] = {}
    for m in batch.members:
        bank, g = m.payload
        k = index.get(id(bank))
        if k is None:
            k = index[id(bank)] = len(banks)
            banks.append(bank)
        slots.append((k, int(g)))
    return banks, [tuple(range(b.n_groups)) for b in banks], slots


def execute_batch(
    batch: CoalescedBatch,
    kernel: KernelFn,
    shift_kernel: ShiftKernelFn,
    multibank_kernel: MultiBankKernelFn | None = None,
) -> list:
    """Run one coalesced batch on the local device; returns one fidelity
    entry per member, in member (submission) order.  Shared by the sync and
    async dispatchers — batch composition never changes per-lane math, so
    both paths are bit-identical.

    Row batches are zero-padded up to a LANES multiple (shape bucketing):
    deadline flushes emit arbitrary partial sizes, and without bucketing
    every new size costs a fresh XLA compile — under the async pump, where
    partial flushes are routine, that recompile storm dwarfs the kernel
    time.  The pad lanes are dead weight the launch already paid for
    (``CoalescedBatch.padded``) and are sliced off before scatter-back."""
    if isinstance(batch.key, ShiftGroupKey):
        # ONE prefix-reuse kernel launch computes every (param, shift)
        # group of every bank in the batch (``bank_partition``); member i
        # gets its group's (B,) fidelity row of its bank's block, cut out
        # by one ``_split_rows`` program per block.
        spec = batch.key.spec
        banks, group_sets, slots = bank_partition(batch)
        if len(banks) == 1:
            rows = shift_kernel(
                spec,
                banks[0].theta,
                banks[0].data,
                banks[0].four_term,
                group_sets[0],
            )
            parts = _split_rows(rows, rows.shape[1])
            return [parts[i] for _, i in slots]
        # per-bank lane bucketing BEFORE the jit boundary: deadline flushes
        # mix arbitrary sample counts, and without rounding each bank to a
        # LANES multiple every new (B_0, B_1, ...) combination would compile
        # a fresh fused kernel — the same recompile storm shape bucketing
        # fixed for row batches.  Pad lanes are per-lane-independent dead
        # weight; slice each member's row back to its bank's true width.
        def bucket(x):
            pad = (-x.shape[0]) % LANES
            return jnp.pad(x, ((0, pad), (0, 0))) if pad else x

        outs = (multibank_kernel or kops.vqc_fidelity_shiftgroups_multibank)(
            spec,
            tuple(bucket(b.theta) for b in banks),
            tuple(bucket(b.data) for b in banks),
            batch.key.four_term,
            tuple(group_sets),
        )
        parts = [_split_rows(out, b.n_samples) for out, b in zip(outs, banks)]
        return [parts[k][i] for k, i in slots]
    spec: CircuitSpec = batch.key
    n = len(batch.members)
    # bucketing to LANES (not the coalescer's possibly-smaller test-time lane
    # config) is free: the Pallas kernel's internal tile is >= LANES lanes
    # for ANY batch size, so the pad rows add zero kernel work while keeping
    # the number of distinct compiled shapes minimal.
    lanes = n + (-n) % LANES
    theta = _stack_rows([m.payload[0] for m in batch.members], lanes)
    data = _stack_rows([m.payload[1] for m in batch.members], lanes)
    fids = kernel(spec, theta, data)
    return [fids[i] for i in range(n)]


def _stack_rows(rows: list, lanes: int):
    """The members' rows stacked and zero-padded to ``lanes``.  Host rows
    are stacked on the host and sent in one transfer; device rows are
    padded as a list, so the stacking program is one per lane bucket and
    not one per member count (a deadline flush's count is arbitrary, and a
    new program compiled on the dispatch path stalls every queued batch)."""
    if all(isinstance(r, np.ndarray) for r in rows):
        out = np.zeros((lanes, *rows[0].shape), np.result_type(*rows))
        out[: len(rows)] = rows
        return jnp.asarray(out)
    return jnp.stack(rows + [jnp.zeros_like(rows[0])] * (lanes - len(rows)))


@functools.partial(jax.jit, static_argnums=1)
def _split_rows(block, n: int) -> tuple:
    """Every row of a bank's ``(G, B)`` output block, cut to its first ``n``
    lanes, as one program with G outputs.  An eager index per member would
    cost a slice and a squeeze, each its own dispatch and allocation; this
    program is keyed only on the block's shape and ``n``, never on which of
    the bank's groups a batch carries (``bank_partition``)."""
    return tuple(block[g, :n] for g in range(block.shape[0]))


# ------------------------------------------------------- analytic cost model
def batch_family(batch: CoalescedBatch):
    """Service-model key: batches of one structural family share an EWMA."""
    if isinstance(batch.key, ShiftGroupKey):
        return ("shift", batch.key.spec)
    return batch.key


def batch_cost_units(batch: CoalescedBatch) -> float:
    """Analytic work units of one batch: gate applications x padded lanes.

    Row batches pay the full gate sequence over their padded lane tile.
    Shift-group batches pay the analytic cost of the path the ops layer
    will actually take (``kernels.shift_cost_info`` on the UNION group
    set): the fused prefix-reuse cost — data-register pass, forward pass,
    backward pass down to the shallowest anchor, and each variant's suffix
    replay (one gate for single-use parameters, the [first, last] span for
    multi-use ones) — over the sum of the banks' padded lane segments,
    since the fused launch computes the union groups for every lane; or,
    when no plan exists / replay is analytically dearer, the per-bank
    materialized fallback cost.
    """
    spec = batch_spec(batch)
    if not isinstance(batch.key, ShiftGroupKey):
        pad = batch.padded(LANES)
        return float(len(spec.ops) * pad)
    banks, group_sets, _ = bank_partition(batch)
    pad_b = sum(math.ceil(b.n_samples / LANES) * LANES for b in banks)
    union = tuple(sorted({g for gs in group_sets for g in gs}))
    cost = shift_cost_info(spec, batch.key.four_term, union)
    if not cost["use_implicit"]:
        # fallback materializes each bank's groups separately
        return float(
            len(spec.ops)
            * sum(
                len(gs) * math.ceil(b.n_samples / LANES) * LANES
                for b, gs in zip(banks, group_sets)
            )
        )
    return float(cost["gate_apps_implicit"] * pad_b)


# ------------------------------------------------------- worker VMEM model
#: modeled per-worker VMEM (one TPU core's worth): batches whose working
#: set exceeds it cannot run on a single worker and spill to the mesh.
WORKER_VMEM_BYTES = 16 * 1024 * 1024


@functools.lru_cache(maxsize=256)
def _launch_plan(spec, lanes: int, four_term: bool, groups: tuple, budget: int) -> dict:
    return shift_execution_info(
        spec, lanes, four_term=four_term, groups=groups, vmem_budget=budget
    )


def shift_launch_info(batch: CoalescedBatch) -> tuple[int, int, dict]:
    """``(banks, lanes, shift_execution_info)`` of the one kernel launch a
    shift-group batch makes: its banks' LANES-padded lane segments side by
    side, the union of their group sets.  The plan is cached per shape and
    VMEM budget (the dispatcher asks on every launch); treat it as
    read-only."""
    banks, group_sets, _ = bank_partition(batch)
    lanes = sum(math.ceil(b.n_samples / LANES) * LANES for b in banks)
    union = tuple(sorted({g for gs in group_sets for g in gs}))
    info = _launch_plan(
        batch.key.spec, lanes, batch.key.four_term, union, kvs.VMEM_BUDGET_BYTES
    )
    return len(banks), lanes, info


def kernel_span_args(batch: CoalescedBatch) -> dict:
    """Trace-span payload for one batch's kernel launch: the shift-plan
    execution shape (``shift_execution_info`` — fused/spill/materialize,
    launches, tiles, VMEM footprint) for shift-group batches, the padded
    row-tile footprint otherwise.  Only computed when tracing is enabled."""
    spec = batch_spec(batch)
    if isinstance(batch.key, ShiftGroupKey):
        n_banks, lanes, info = shift_launch_info(batch)
        args = {
            "kind": "shift",
            "mode": info["mode"],
            "launches": info["launches"],
            "n_tiles": info["n_tiles"],
            "vmem_bytes": info["vmem_bytes"],
            "banks": n_banks,
            "lanes": lanes,
            "members": batch.n,
        }
        if info["mode"] == "spill":
            # boundary-fetch shape of the double-buffered backward launch:
            # n_tiles fetches ping-ponging two VMEM buffers, all but the
            # first overlapping the previous tile's compute.
            args["spill_buffer_bytes"] = info["spill_buffer_bytes"]
            args["boundary_fetches"] = info["n_tiles"]
            args["overlap_ratio"] = info["overlap_ratio"]
        return args
    padded = batch.padded(LANES)
    return {
        "kind": "rows",
        "mode": "rows",
        "launches": 1,
        "vmem_bytes": 2 * 4 * (2**spec.n_qubits) * kernel_tb(padded),
        "lanes": padded,
        "members": batch.n,
    }


def batch_vmem_bytes(batch: CoalescedBatch) -> int:
    """Modeled single-worker VMEM working set of one coalesced batch.

    Row batches hold the full 2**n-dim statevector tile ((re, im) float32
    at the kernel's lane-tile width).  Shift-group batches hold the
    register-local checkpoint set — already bounded by the kernel's own
    depth-tile spilling, so ``shift_execution_info`` reports the post-spill
    footprint.  The dispatcher compares this against ``WORKER_VMEM_BYTES``
    to decide mesh spill (the whole-mesh path shards lanes, shrinking the
    per-device tile back under budget)."""
    spec = batch_spec(batch)
    if isinstance(batch.key, ShiftGroupKey):
        return shift_launch_info(batch)[2]["vmem_bytes"]
    tb = kernel_tb(batch.padded(LANES))
    return 2 * 4 * (2**spec.n_qubits) * tb


class Dispatcher:
    """The dispatch core and its synchronous driver.

    Both drivers reach a batch's execution through the same three steps:
    ``_place`` (Algorithm 2 placement and the CRU charge), ``_execute``
    (retries, breaker, migration, terminal failure, settle) and
    ``_execute_spilled`` (the whole-mesh path).  This class calls them
    inline; ``AsyncDispatcher`` calls them from its pump thread and worker
    pool, and keeps its slot bookkeeping in the ``_release`` hook."""

    def __init__(
        self,
        gateway: Gateway,
        workers: Sequence[WorkerConfig],
        *,
        manager: CoManager | None = None,
        kernel: KernelFn | None = None,
        shift_kernel: ShiftKernelFn | None = None,
        multibank_kernel: MultiBankKernelFn | None = None,
        mesh_spill: bool = True,
        spill_executor=None,
        worker_vmem_bytes: int = WORKER_VMEM_BYTES,
        clock=time.perf_counter,
        fault_tolerance: FaultToleranceConfig | None = None,
        fault_injector: FaultInjector | None = None,
    ):
        self.gateway = gateway
        self.manager = manager or CoManager(multi_tenant=True)
        self.kernel = kernel or kops.vqc_fidelity
        self.shift_kernel = shift_kernel or kops.vqc_fidelity_shiftgroups
        self.multibank_kernel = (
            multibank_kernel or kops.vqc_fidelity_shiftgroups_multibank
        )
        #: route mega-batches that fit no single worker (register width or
        #: VMEM model) through the whole-mesh sharded executor instead of
        #: failing fast; disable to restore the strict fail-fast contract.
        self.mesh_spill = mesh_spill
        self.worker_vmem_bytes = worker_vmem_bytes
        self._spill = spill_executor  # built lazily when None
        self.clock = clock
        self.task_ids = TaskIdAllocator()
        self.batch_log: list[tuple[str, int, tuple]] = []  # (worker, n, clients)
        self._base_cru: dict[str, float] = {}
        self._outstanding_s: dict[str, float] = {}  # predicted queued seconds
        self.ft = fault_tolerance or FaultToleranceConfig()
        self.fleet = FleetHealth(self.ft)
        self.fault_injector = fault_injector
        if fault_injector is not None:
            fault_injector.start(self.clock())
        #: guards the co-Manager views, the runner registry and
        #: ``batch_log``; only the async driver's threads contend for it
        self._cv = threading.Condition()
        # in-flight runner registry for hedging and first-result-wins:
        # id(batch) -> {batch, outstanding, winner, wid, t0, est, hedged}
        self._runners: dict[int, dict] = {}
        self._max_width = max((w.max_qubits for w in workers), default=0)
        for w in workers:
            self._register(w)

    # ------------------------------------------------------ live membership
    def _register(self, w: WorkerConfig) -> None:
        self.manager.register_worker(
            w.worker_id,
            w.max_qubits,
            cru=w.base_load,
            t=self.clock(),
            error_rate=w.error_rate,
        )
        self._base_cru[w.worker_id] = w.base_load
        self._outstanding_s[w.worker_id] = 0.0
        self.fleet.add(w.worker_id)

    def _recompute_max_width(self) -> None:
        self._max_width = max(
            (v.max_qubits for v in self.manager.workers.values()), default=0
        )

    def register_worker(self, worker: WorkerConfig) -> None:
        """Add a worker to the fleet at runtime; it becomes placeable on
        the next batch."""
        if worker.worker_id in self._base_cru:
            raise ValueError(f"worker {worker.worker_id!r} already registered")
        self._register(worker)
        self._max_width = max(self._max_width, worker.max_qubits)

    def drain_worker(self, worker_id: str, timeout: float = 30.0) -> None:
        """Remove a worker from the fleet: stop placing on it, let in-flight
        work land, then forget it.  The sync dispatcher has no cross-call
        in-flight work, so removal is immediate."""
        if worker_id not in self._base_cru:
            raise KeyError(f"unknown worker {worker_id!r}")
        self.fleet.mark_draining(worker_id)
        self._forget_worker(worker_id)

    def _forget_worker(self, worker_id: str) -> None:
        self.manager.workers.pop(worker_id, None)
        self._base_cru.pop(worker_id, None)
        self._outstanding_s.pop(worker_id, None)
        self.fleet.remove(worker_id)
        self._recompute_max_width()

    # ------------------------------------------------------ CRU cost model
    def _estimate_s(self, batch: CoalescedBatch) -> float:
        return self.gateway.telemetry.service.estimate(
            batch_family(batch), batch_cost_units(batch)
        )

    def _charge(self, wid: str, seconds: float) -> None:
        """Add/remove predicted outstanding work from a worker's CRU: the
        EWMA service estimate is the co-Manager's view of classical load."""
        self._outstanding_s[wid] = max(
            0.0, self._outstanding_s.get(wid, 0.0) + seconds
        )
        view = self.manager.workers.get(wid)
        if view is not None:
            view.cru = self._base_cru.get(wid, 0.0) + self._outstanding_s[wid]

    def _observe(self, batch: CoalescedBatch, seconds: float) -> None:
        self.gateway.telemetry.service.update(
            batch_family(batch), batch_cost_units(batch), seconds
        )

    # ----------------------------------------------------------- execution
    @staticmethod
    def _width(batch: CoalescedBatch) -> int:
        return batch_spec(batch).n_qubits

    def _oversized(self, batch: CoalescedBatch) -> bool:
        """No single worker can run this batch: register width above every
        worker's capacity, or working set over the per-worker VMEM model.
        Memoized on the batch — composition is immutable after coalescing,
        and the async ready-queue scan re-asks on every placement pass
        (often under its condition lock)."""
        verdict = getattr(batch, "_oversized_verdict", None)
        if verdict is None:
            verdict = (
                self._width(batch) > self._max_width
                or batch_vmem_bytes(batch) > self.worker_vmem_bytes
            )
            batch._oversized_verdict = verdict
        return verdict

    def _spill_executor(self):
        if self._spill is None:
            from repro.comanager.dataplane import MeshSpillExecutor

            self._spill = MeshSpillExecutor()
        return self._spill

    def _spill_fns(self):
        """(kernel, shift_kernel, multibank_kernel) triple backed by the
        whole-mesh spill executor, so ``execute_batch`` runs unchanged."""
        ex = self._spill_executor()
        return (
            lambda spec, t, d: ex.rows(spec, t, d),
            lambda spec, t, d, ft, gs: ex.banks(
                spec, (t,), (d,), ft, (tuple(gs),)
            )[0],
            lambda spec, ts, ds, ft, gss: ex.banks(spec, ts, ds, ft, gss),
        )

    def _record(self, batch: CoalescedBatch) -> None:
        """Per-launch telemetry shared by the sync and async paths."""
        if isinstance(batch.key, ShiftGroupKey):
            banks, _, _ = bank_partition(batch)
            self.gateway.telemetry.on_fused_launch(len(banks))

    def _record_spill(self, batch: CoalescedBatch) -> None:
        """A shift-group batch a worker ran through the depth-tiled spill
        kernel counts its banks, depth tiles and the boundary states it
        moved through HBM (``Telemetry.on_spill_launch``).  The whole-mesh
        path shards lanes and tiles on its own and does not call this."""
        if not isinstance(batch.key, ShiftGroupKey):
            return
        n_banks, lanes, info = shift_launch_info(batch)
        if info["mode"] == "spill":
            blocks = math.ceil(lanes / kernel_tb(lanes))
            self.gateway.telemetry.on_spill_launch(
                n_banks, info["n_tiles"], info["spilled_bytes"] * blocks
            )

    def _stage(self, batch: CoalescedBatch, now: float, *stages, worker=None):
        """Record trace ``stages`` at ``now`` for every member of ``batch``."""
        tr = self.gateway.telemetry.trace
        if tr.enabled:
            seqs = [m.seq for m in batch.members]
            for stage in stages:
                tr.batch_stage(seqs, stage, now, worker)

    # ----------------------------------------------------- the dispatch core
    def _place(self, batch: CoalescedBatch, now: float, exclude):
        """Algorithm 2 placement on a worker outside ``exclude``: returns
        ``(task, wid, est)`` with the EWMA estimate ``est`` charged to the
        worker's CRU, or None when no worker fits.  The async driver calls
        this under ``_cv``."""
        est = self._estimate_s(batch)
        task = CircuitTask(
            task_id=next(self.task_ids),
            client_id="gateway",
            demand=self._width(batch),
            service_time=est,
        )
        wid = self.manager.assign(task, now, exclude=exclude)
        if wid is None:
            return None
        self._charge(wid, est)
        self.fleet.on_dispatch(wid)
        self._stage(batch, now, "placed", worker=wid)
        return task, wid, est

    def _execute(
        self,
        batch: CoalescedBatch,
        task: CircuitTask | None,
        wid: str,
        est: float,
        hedge: bool = False,
    ) -> BaseException | None:
        """Execute one placed batch on ``wid``, resolve or hand on its
        futures, release the worker.  Returns the error that failed the
        futures; None when the batch completed or migrated.

        Failure tolerance: a failed attempt retries in place (bounded by
        ``FaultToleranceConfig.retry_limit`` with exponential backoff), then
        the batch migrates to a surviving worker through the gateway's
        re-coalescing requeue.  With hedging, two runners may race on one
        batch: the first success claims it (resolving the futures exactly
        once) and the loser's result is discarded — kernel launches cannot
        be interrupted, so safe cancellation means the loser lands without
        side effects."""
        tel = self.gateway.telemetry
        tr = tel.trace
        if not hedge:
            self._stage(batch, self.clock(), "dispatched", "kernel_start")
        fids = None
        attempts = 0
        while True:
            t0 = self.clock()
            try:
                if self.fault_injector is not None:
                    self.fault_injector.check(wid, t0)
                with span("dispatcher.execute", tr):
                    fids = execute_batch(
                        batch, self.kernel, self.shift_kernel, self.multibank_kernel
                    )
                if self.fault_injector is not None:
                    # mirror the simulation's slowdown fault in wall time
                    extra = (
                        self.fault_injector.slowdown_factor(wid, t0) - 1.0
                    ) * (self.clock() - t0)
                    if extra > 0:
                        time.sleep(extra)
                err = None
                break
            except BaseException as exc:
                err = exc
            now = self.clock()
            tripped = self.fleet.on_failure(wid, now)
            tel.on_worker_failure(wid)
            if tripped:
                tel.on_worker_offline(wid)
                self._stage(batch, now, "worker_offline", worker=wid)
            attempts += 1
            if (
                hedge
                or attempts > self.ft.retry_limit
                or not self.fleet.retryable(wid, now)
            ):
                break
            self.fleet.record_retry(wid)
            tel.on_worker_retry(wid)
            self._stage(batch, now, "retried", worker=wid)
            if self.ft.retry_backoff_s:
                time.sleep(self.ft.retry_backoff_s * 2 ** (attempts - 1))
        now = self.clock()
        # settle against the (possibly hedged) runner set: the first
        # successful runner claims the batch, the LAST failed runner with
        # no winner owns migration or terminal failure.
        with self._cv:
            entry = self._runners.get(id(batch))
            if entry is None:  # the batch's only runner
                claimed, owner = err is None, err is not None
            else:
                entry["outstanding"] -= 1
                claimed = err is None and entry["winner"] is None
                if claimed:
                    entry["winner"] = wid
                last = entry["outstanding"] <= 0
                owner = err is not None and last and entry["winner"] is None
                if last:
                    del self._runners[id(batch)]
        failed = None
        if claimed:
            if tr.enabled:
                tr.worker_span(wid, t0, now, args=kernel_span_args(batch))
            self._observe(batch, now - t0)
            self._record(batch)
            self._record_spill(batch)
            self.gateway.complete(batch, fids, now)
        elif owner:
            bad = self.fleet.unplaceable(now)
            with self._cv:
                survivor = any(
                    w != wid and w not in bad and v.max_qubits >= self._width(batch)
                    for w, v in self.manager.workers.items()
                )
            if survivor:
                self.fleet.record_migration(wid)
                tel.on_worker_migration(wid)
                self._stage(batch, now, "migrated", worker=wid)
                self.gateway.requeue(batch, now)
            else:
                failed = err
                self.gateway.fail(batch, err, now)
        if err is None:
            self.fleet.on_success(wid)
        # futures are resolved BEFORE the worker is released, so a driver
        # that counts releases knows every released batch's futures landed.
        with self._cv:
            if task is not None:
                self.manager.complete(wid, task, now)
            self._charge(wid, -est)
            self.fleet.on_release(wid)
            if claimed or owner:
                self.batch_log.append((wid, batch.n, tuple(sorted(batch.clients()))))
            self._release(wid, failed)
        return failed

    def _execute_spilled(self, batch: CoalescedBatch) -> BaseException | None:
        """Execute one oversized batch on the whole device mesh (no single
        worker is charged — the spill path is its own resource) and resolve
        its futures.  Returns the error that failed them, if any."""
        tel = self.gateway.telemetry
        t0 = self.clock()
        self._stage(batch, t0, "dispatched", "kernel_start")
        err = fids = None
        try:
            with span("dispatcher.execute", tel.trace):
                fids = execute_batch(batch, *self._spill_fns())
        except BaseException as exc:
            err = exc
        now = self.clock()
        if err is None:
            if tel.trace.enabled:
                tel.trace.worker_span(
                    "mesh", t0, now, kind="spill", args=kernel_span_args(batch)
                )
            tel.service.update(
                ("spill", batch_family(batch)), batch_cost_units(batch), now - t0
            )
            tel.on_spill(batch.lane_count)
            self._record(batch)
            self.gateway.complete(batch, fids, now)
        else:
            self.gateway.fail(batch, err, now)
        with self._cv:
            self.batch_log.append(("mesh", batch.n, tuple(sorted(batch.clients()))))
            self._release(None, err)
        return err

    def _release(self, wid: str | None, err: BaseException | None) -> None:
        """Driver hook, called under ``_cv`` once a batch has settled on
        worker ``wid`` (None: the mesh); ``err`` failed its futures."""

    # ---------------------------------------------------------- sync driver
    def run_spilled(self, batch: CoalescedBatch) -> str:
        """Execute one oversized batch on the whole device mesh, inline."""
        self._stage(batch, self.clock(), "placed", worker="mesh")
        err = self._execute_spilled(batch)
        if err is not None:
            raise err
        return "mesh"

    def run_batch(self, batch: CoalescedBatch) -> str:
        """Place one batch via Algorithm 2 and execute it inline (retrying,
        then migrating through the gateway's requeue on failure).  Raises
        the error that failed the batch's futures."""
        if self.mesh_spill and self._oversized(batch):
            return self.run_spilled(batch)
        now = self.clock()
        placed = self._place(batch, now, self.fleet.unplaceable(now))
        if placed is None:
            if self.mesh_spill:
                return self.run_spilled(batch)
            caps = [v.max_qubits for v in self.manager.workers.values()]
            err = RuntimeError(
                f"no worker fits a {self._width(batch)}-qubit batch "
                f"(capacities: {caps})"
            )
            self.gateway.fail(batch, err, now)
            raise err
        err = self._execute(batch, *placed)
        if err is not None:
            raise err
        return placed[1]

    # ---------------------------------------------------------------- pump
    def pump(self) -> int:
        """Coalesce what's admitted; run every emitted batch.  Returns the
        number of batches executed."""
        batches = self.gateway.pump(self.clock())
        for b in batches:
            self.run_batch(b)
        return len(batches)

    def drain(self) -> int:
        """Force-flush partial buffers and run everything (end of a bank).
        Loops until the gateway is empty so batches migrated back through
        the coalescer after a worker failure are re-emitted and re-placed."""
        n = 0
        while True:
            batches = self.gateway.flush(self.clock())
            if not batches:
                return n
            for b in batches:
                self.run_batch(b)
            n += len(batches)

    # lifecycle no-ops so sync/async runtimes share a shutdown path
    def start(self) -> None:
        pass

    def kick(self) -> None:
        pass

    def close(self) -> None:
        pass

    def absorb_backpressure(self) -> None:
        """A tenant queue is full: inline execution is the only way the sync
        dispatcher frees it (the async override waits for a completion
        instead of quiescing everything)."""
        self.drain()


class GatewayRuntime:
    """Bundled gateway + dispatcher + telemetry for local serving.

    The unit the trainer and the benchmarks hold on to: multiple training
    clients share one runtime, and their circuit banks coalesce across
    tenants into shared kernel launches.

    ``mode``: "sync" executes each mega-batch inline on the submitting
    thread; "async" starts an ``AsyncDispatcher`` — a pump thread plus a
    per-worker execution pool (``slots_per_worker`` in-flight mega-batches
    per worker), so kernel execution overlaps with admission, coalescing,
    and placement, and futures resolve out of order.
    """

    def __init__(
        self,
        workers: Sequence[WorkerConfig] | None = None,
        *,
        target: int | None = None,
        deadline: float = 1.0,
        kernel: KernelFn | None = None,
        shift_kernel: ShiftKernelFn | None = None,
        multibank_kernel: MultiBankKernelFn | None = None,
        mesh_spill: bool = True,
        spill_executor=None,
        worker_vmem_bytes: int = WORKER_VMEM_BYTES,
        evict_over_slo: bool = False,
        clock=time.perf_counter,
        mode: str = "sync",
        slots_per_worker: int = 1,
        observability=None,
        fault_tolerance: FaultToleranceConfig | None = None,
        fault_injector: FaultInjector | None = None,
        **gateway_opts,
    ):
        if mode not in ("sync", "async"):
            raise ValueError(f"unknown mode {mode!r}")
        if workers is None:
            workers = [
                WorkerConfig(f"w{i + 1}", q) for i, q in enumerate((5, 10, 15, 20))
            ]
        self.mode = mode
        self.telemetry = Telemetry(observability=observability)
        self.gateway = Gateway(
            target=target,
            deadline=deadline,
            telemetry=self.telemetry,
            **gateway_opts,
        )
        common = dict(
            kernel=kernel,
            shift_kernel=shift_kernel,
            multibank_kernel=multibank_kernel,
            mesh_spill=mesh_spill,
            spill_executor=spill_executor,
            worker_vmem_bytes=worker_vmem_bytes,
            clock=clock,
            fault_tolerance=fault_tolerance,
            fault_injector=fault_injector,
        )
        if mode == "async":
            from repro.serve.async_dispatcher import AsyncDispatcher

            self.dispatcher = AsyncDispatcher(
                self.gateway,
                workers,
                slots_per_worker=slots_per_worker,
                evict_over_slo=evict_over_slo,
                **common,
            )
        else:
            if evict_over_slo:
                raise ValueError(
                    "evict_over_slo requires mode='async' "
                    "(the sync dispatcher has no ready queue)"
                )
            self.dispatcher = Dispatcher(self.gateway, workers, **common)
        # kernel profiling hook: shift-plan launches report their execution
        # shape (fused/spill/materialize) to this runtime's recorder for as
        # long as the runtime is open; restored on close so runtimes nest.
        self._prev_observer = None
        self._observer_installed = False
        if self.telemetry.trace.enabled:
            self._prev_observer = kops.set_launch_observer(
                self.telemetry.trace.on_kernel_launch
            )
            self._observer_installed = True
        self.dispatcher.start()

    def close(self) -> None:
        """Stop the pump thread and worker pool (async mode; sync no-op)."""
        if self._observer_installed:
            kops.set_launch_observer(self._prev_observer)
            self._observer_installed = False
        self.dispatcher.close()

    def __enter__(self) -> "GatewayRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _submit_all(self, client_id: str, key, payloads, lanes: int) -> list:
        """Admit every payload under ``key``, wake the dispatcher after each,
        drain, and return the values in submission order."""
        futures = []
        for payload in payloads:
            while True:
                try:
                    futures.append(
                        self.gateway.submit(
                            client_id,
                            key,
                            payload,
                            now=self.dispatcher.clock(),
                            lanes=lanes,
                        )
                    )
                    break
                except Backpressure:
                    # sync: drain in-flight work; async: wait for a
                    # completion to free queue space without quiescing
                    self.dispatcher.absorb_backpressure()
            self.dispatcher.kick()
        self.dispatcher.drain()
        return [f.value for f in futures]

    def executor(
        self,
        spec: CircuitSpec,
        client_id: str,
        *,
        weight: float = 1.0,
        priority: int = 1,
        slo_ms: float | None = None,
    ):
        """A ``shift_rule.Executor`` that routes a circuit bank through the
        gateway row by row and gathers fidelities in submission order —
        ``shift_rule.assemble_gradient`` consumes the result unchanged.

        In async mode submission overlaps with execution: rows stream into
        the pump loop as they are admitted, and the final gather blocks on
        the out-of-order futures."""
        if client_id not in self.gateway.tenants:
            self.gateway.register_client(
                client_id, weight=weight, priority=priority, slo_ms=slo_ms
            )

        def run(theta_bank: jnp.ndarray, data_bank: jnp.ndarray) -> jnp.ndarray:
            rows = ((theta_bank[i], data_bank[i]) for i in range(theta_bank.shape[0]))
            return jnp.stack(self._submit_all(client_id, spec, rows, 1))

        return run

    def shift_executor(
        self,
        spec: CircuitSpec,
        client_id: str,
        *,
        weight: float = 1.0,
        priority: int = 1,
        slo_ms: float | None = None,
    ):
        """A shift-aware ``shift_rule.Executor``: an implicit ``ShiftBank``
        enters the gateway as per-(param, shift) GROUP subtasks — 1 + 2P
        admissions instead of (1 + 2P) * B — which the coalescer packs into
        joint prefix-reuse kernel launches and the co-Manager places as
        whole-batch tasks.  Batches are keyed by circuit STRUCTURE
        (``ShiftGroupKey``), so concurrent tenants training the same spec
        fuse their banks' subtasks into shared multi-bank launches.  Group
        fidelities come back in bank order, so
        ``shift_rule.assemble_gradient`` consumes them unchanged.

        Plain ``(theta_bank, data_bank)`` calls are also accepted and fall
        back to per-row submission, so the executor composes with every bank
        mode."""
        row_run = self.executor(
            spec, client_id, weight=weight, priority=priority, slo_ms=slo_ms
        )

        def run(bank, data_bank=None) -> jnp.ndarray:
            if data_bank is not None:
                return row_run(bank, data_bank)
            groups = ((bank, g) for g in range(bank.n_groups))
            key = ShiftGroupKey(spec, bank.four_term)
            return jnp.concatenate(
                self._submit_all(client_id, key, groups, bank.n_samples)
            )

        return declare(run, shiftbank=True)
