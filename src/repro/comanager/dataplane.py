"""Data plane: really execute circuit banks, per co-Manager assignment.

Two execution paths:

* ``worker_batched_executor`` — groups the bank rows assigned to each worker
  and runs each group through the fused Pallas VQC kernel.  This is the
  faithful "each worker executes its circuits" path; on one host the groups
  run sequentially, on a pod each worker's group lands on its mesh slice.

* ``sharded_executor`` — the TPU-native whole-bank path: the bank is sharded
  over the mesh's ``data`` axis with ``shard_map`` and every device runs the
  kernel on its shard.  This is what the production launcher uses and what
  the multi-pod dry-run lowers.

Both return fidelities in bank order, so ``shift_rule.assemble_gradient``
consumes them identically — scheduling never changes the math (the accuracy
experiments in the paper rely on exactly this property).

Both executors also accept IMPLICIT ``shift_rule.ShiftBank``s (call
``run(bank)``): the schedulable unit then becomes the (param, shift) group
and execution goes through the prefix-reuse kernel — same bank-order
results, a fraction of the gate applications and angle traffic.

Every factory here returns a ``declare``-d executor; the
``repro.api.backend`` adapters lift them into the ``ExecutionBackend``
protocol.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.api.capabilities import declare
from repro.core import shift_rule
from repro.core.sim import CircuitSpec
from repro.kernels import ops as kops


def worker_batched_executor(
    spec: CircuitSpec, assignment: Sequence[int], n_workers: int
):
    """Executor that mimics per-worker execution.

    Materialized banks: ``assignment[i] = worker index for bank row i``.
    Rows are grouped per worker and executed as one fused-kernel batch each;
    results come back in bank order via ONE inverse-permutation gather
    (rather than a per-worker scatter loop of ``out.at[rows].set``, which
    built n_workers intermediate arrays).

    Implicit ``ShiftBank``s (``run(bank)``): the schedulable unit becomes the
    (param, shift) GROUP — ``assignment[g] = worker index for bank group g``
    (length ``bank.n_groups``) — and each worker executes its groups as one
    prefix-reuse kernel call over the whole sample batch, so the co-Manager
    distributes suffix-replay subtasks instead of materialized rows.
    """
    import numpy as np

    assignment = np.asarray(assignment)
    # stable grouping permutation: rows sorted by worker, ties in bank order,
    # so each worker's group preserves its clients' submission order.
    order = np.argsort(assignment, kind="stable")
    inverse = np.argsort(order, kind="stable")
    bounds = np.searchsorted(assignment[order], np.arange(n_workers + 1))
    inverse_j = jnp.asarray(inverse)

    def _run_rows(theta_bank: jnp.ndarray, data_bank: jnp.ndarray) -> jnp.ndarray:
        groups = []
        for w in range(n_workers):
            rows = order[bounds[w] : bounds[w + 1]]
            if rows.size == 0:
                continue
            groups.append(kops.vqc_fidelity(spec, theta_bank[rows], data_bank[rows]))
        return jnp.concatenate(groups)[inverse_j]

    def _run_shiftbank(bank: shift_rule.ShiftBank) -> jnp.ndarray:
        if len(assignment) != bank.n_groups:
            if len(assignment) == bank.n_circuits:
                # per-ROW assignment (legacy scheduling granularity): honor it
                # exactly by materializing — same per-worker row placement.
                mat = bank.materialize()
                return _run_rows(mat.theta, mat.data)
            raise ValueError(
                f"assignment must cover the bank's {bank.n_groups} groups or "
                f"{bank.n_circuits} rows, got {len(assignment)} entries"
            )
        outs = []
        for w in range(n_workers):
            grp = order[bounds[w] : bounds[w + 1]]
            if grp.size == 0:
                continue
            outs.append(
                kops.vqc_fidelity_shiftgroups(
                    spec,
                    bank.theta,
                    bank.data,
                    bank.four_term,
                    tuple(int(g) for g in grp),
                )
            )
        stacked = jnp.concatenate(outs, 0)[inverse_j]  # (n_groups, B)
        return stacked.reshape(-1)

    def run(theta_bank, data_bank=None):
        if isinstance(theta_bank, shift_rule.ShiftBank):
            return _run_shiftbank(theta_bank)
        return _run_rows(theta_bank, data_bank)

    return declare(run, shiftbank=True)


def round_robin_assignment(n_circuits: int, n_workers: int):
    """The degenerate scheduler baseline (no co-management).

    Also the group-assignment baseline for implicit banks (pass
    ``n_circuits = bank.n_groups``)."""
    return [i % n_workers for i in range(n_circuits)]


def worker_pool_executor(
    spec: CircuitSpec,
    assignment: Sequence[int],
    n_workers: int,
    max_threads: int | None = None,
):
    """``worker_batched_executor`` with OVERLAPPING per-worker execution.

    The sequential executor runs each worker's group one after another on
    the host — faithful to one device, but on a multi-worker host (or with
    XLA releasing the GIL during kernel execution) the groups can run
    concurrently, exactly like the async dispatcher's one-slot-per-worker
    pool.  Each worker's group is submitted to a thread pool; results gather
    in bank order, so gradients are bit-identical to the sequential path
    (scheduling never changes the math).

    Call ``run.close()`` to shut the pool down when the executor is retired
    (threads are created on demand, so an unused executor costs nothing).
    """
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor

    assignment = np.asarray(assignment)
    order = np.argsort(assignment, kind="stable")
    inverse = np.argsort(order, kind="stable")
    bounds = np.searchsorted(assignment[order], np.arange(n_workers + 1))
    inverse_j = jnp.asarray(inverse)
    pool = ThreadPoolExecutor(
        max_workers=max_threads or n_workers, thread_name_prefix="dataplane-worker"
    )

    def _groups():
        for w in range(n_workers):
            rows = order[bounds[w] : bounds[w + 1]]
            if rows.size:
                yield w, rows

    def run(theta_bank, data_bank=None) -> jnp.ndarray:
        if isinstance(theta_bank, shift_rule.ShiftBank):
            bank = theta_bank
            if len(assignment) != bank.n_groups:
                if len(assignment) == bank.n_circuits:
                    # per-ROW assignment (legacy granularity): honor it by
                    # materializing, same as worker_batched_executor.
                    mat = bank.materialize()
                    return run(mat.theta, mat.data)
                raise ValueError(
                    f"assignment must cover the bank's {bank.n_groups} "
                    f"groups or {bank.n_circuits} rows, got "
                    f"{len(assignment)} entries"
                )
            futs = [
                pool.submit(
                    kops.vqc_fidelity_shiftgroups,
                    spec,
                    bank.theta,
                    bank.data,
                    bank.four_term,
                    tuple(int(g) for g in rows),
                )
                for _, rows in _groups()
            ]
            stacked = jnp.concatenate([f.result() for f in futs], 0)
            return stacked[inverse_j].reshape(-1)
        futs = [
            pool.submit(kops.vqc_fidelity, spec, theta_bank[rows], data_bank[rows])
            for _, rows in _groups()
        ]
        return jnp.concatenate([f.result() for f in futs])[inverse_j]

    run.close = lambda: pool.shutdown(wait=True)
    return declare(run, shiftbank=True)


def worker_multibank_executor(
    spec: CircuitSpec, assignment: Sequence[int], n_workers: int
):
    """Multi-bank scheduling: the schedulable unit is the (bank, group)
    subtask of a same-spec BANK SET.

    ``assignment[i]`` is the worker for flat subtask i, where subtasks
    enumerate every bank's groups in bank-major order (bank 0 groups
    0..G-1, bank 1 groups 0..G-1, ...).  Each worker executes ALL its
    subtasks — possibly spanning several banks — as ONE fused multi-bank
    prefix-reuse launch, so K co-scheduled tenant banks cost per-worker
    launches instead of K x per-worker launches.  Returns per-bank flat
    fidelity vectors in bank order (``run(banks) -> [f_0, f_1, ...]``) —
    ``shift_rule.assemble_gradient`` consumes each unchanged.
    """
    import numpy as np

    assignment = np.asarray(assignment)

    def run(banks: Sequence[shift_rule.ShiftBank]) -> list:
        if len({b.four_term for b in banks}) > 1:
            raise ValueError("banks in one fused set must share four_term")
        flat = [(bi, g) for bi, b in enumerate(banks) for g in range(b.n_groups)]
        if len(assignment) != len(flat):
            raise ValueError(
                f"assignment must cover the bank set's {len(flat)} "
                f"(bank, group) subtasks, got {len(assignment)} entries"
            )
        grids = [[None] * b.n_groups for b in banks]
        for w in range(n_workers):
            subtasks = [flat[i] for i in np.flatnonzero(assignment == w)]
            if not subtasks:
                continue
            w_banks, group_sets, slots = [], [], []
            index: dict[int, int] = {}
            for bi, g in subtasks:
                k = index.get(bi)
                if k is None:
                    k = index[bi] = len(w_banks)
                    w_banks.append(bi)
                    group_sets.append([])
                slots.append((k, len(group_sets[k])))
                group_sets[k].append(g)
            outs = kops.vqc_fidelity_shiftgroups_multibank(
                spec,
                tuple(banks[bi].theta for bi in w_banks),
                tuple(banks[bi].data for bi in w_banks),
                banks[0].four_term,
                tuple(tuple(gs) for gs in group_sets),
            )
            for (bi, g), (k, i) in zip(subtasks, slots):
                grids[bi][g] = outs[k][i]
        return [jnp.stack(rows, 0).reshape(-1) for rows in grids]

    return declare(run, multibank=True)


def sharded_executor(spec: CircuitSpec, mesh: Mesh, axis: str = "data"):
    """Whole-bank shard_map executor over one mesh axis.

    Materialized banks: pads the bank to a multiple of the axis size, shards
    rows, runs the fused kernel per device, gathers results.  Lowerable with
    ShapeDtypeStructs for the dry-run.

    Implicit ``ShiftBank``s (``run(bank)``): SAMPLES are sharded instead of
    materialized rows — every device runs the prefix-reuse kernel over its
    sample shard and produces all (param, shift) groups for it; the gathered
    (n_groups, B) grid flattens back to bank order.
    """
    n_shards = mesh.shape[axis]

    def _local(theta, data):
        return kops.vqc_fidelity(spec, theta, data)

    shard_fn = jax.shard_map(
        _local,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=P(axis),
        # the Pallas call inside produces ShapeDtypeStructs without vma
        # annotations; skip the varying-across-mesh check.
        check_vma=False,
    )

    shift_fns: dict[bool, Callable] = {}

    def _shift_fn(four_term: bool):
        if four_term not in shift_fns:

            def _local_shift(theta, data):
                return kops.vqc_fidelity_shiftgroups(spec, theta, data, four_term)

            shift_fns[four_term] = jax.shard_map(
                _local_shift,
                mesh=mesh,
                in_specs=(P(axis, None), P(axis, None)),
                out_specs=P(None, axis),
                check_vma=False,
            )
        return shift_fns[four_term]

    group_fns: dict[tuple, Callable] = {}

    def _group_fn(four_term: bool, groups: tuple):
        key = (four_term, groups)
        if key not in group_fns:

            def _local_groups(theta, data):
                return kops.vqc_fidelity_shiftgroups(
                    spec, theta, data, four_term, groups
                )

            group_fns[key] = jax.shard_map(
                _local_groups,
                mesh=mesh,
                in_specs=(P(axis, None), P(axis, None)),
                out_specs=P(None, axis),
                check_vma=False,
            )
        return group_fns[key]

    def run(theta_bank, data_bank=None) -> jnp.ndarray:
        if isinstance(theta_bank, shift_rule.ShiftBank):
            bank = theta_bank
            b = bank.n_samples
            pad = (-b) % n_shards
            t = jnp.pad(bank.theta, ((0, pad), (0, 0)))
            d = jnp.pad(bank.data, ((0, pad), (0, 0)))
            out = _shift_fn(bank.four_term)(t, d)  # (n_groups, B+pad)
            return out[:, :b].reshape(-1)
        c = theta_bank.shape[0]
        pad = (-c) % n_shards
        t = jnp.pad(theta_bank, ((0, pad), (0, 0)))
        d = jnp.pad(data_bank, ((0, pad), (0, 0)))
        return shard_fn(t, d)[:c]

    def run_banks(thetas, datas, four_term: bool, group_sets: tuple):
        """Fused multi-bank launch SHARDED over the mesh: per-bank
        LANES-padded lane segments concatenate, the union group set runs on
        every device's lane shard, and per-bank blocks slice back out —
        the contract of ``kops.vqc_fidelity_shiftgroups_multibank`` with
        the device mesh as the executor (the dispatcher's mega-batch spill
        path)."""
        union = tuple(sorted({g for gs in group_sets for g in gs}))
        theta_cat, data_cat, segments = kops._pack_banks(thetas, datas)
        lanes = theta_cat.shape[0]
        pad = (-lanes) % n_shards
        theta_cat = jnp.pad(theta_cat, ((0, pad), (0, 0)))
        data_cat = jnp.pad(data_cat, ((0, pad), (0, 0)))
        out = jnp.clip(_group_fn(four_term, union)(theta_cat, data_cat), 0.0, 1.0)
        row = {g: i for i, g in enumerate(union)}
        return tuple(
            jnp.stack([out[row[g], off : off + b] for g in gs], axis=0)
            for (off, b), gs in zip(segments, group_sets)
        )

    run.run_banks = run_banks
    return declare(run, shiftbank=True, sharded=True)


class MeshSpillExecutor:
    """Whole-mesh escape hatch for mega-batches that fit no single worker.

    A coalesced batch too wide (qubit count above every worker's register
    capacity) or too deep (statevector tile over the per-worker VMEM model)
    is routed HERE instead of failing fast: row batches shard their lanes
    across the mesh's ``data`` axis, shift-group bank sets run the fused
    multi-bank kernel with lane segments sharded the same way.  Per-spec
    sharded executors are built lazily and cached — a long-lived dispatcher
    pays the shard_map trace once per circuit structure.
    """

    def __init__(self, mesh: Mesh | None = None, axis: str = "data"):
        if mesh is None:
            from repro.launch.mesh import make_data_mesh

            mesh = make_data_mesh()  # every local device on ``data``
        self.mesh = mesh
        self.axis = axis
        self._per_spec: dict[CircuitSpec, Callable] = {}

    def _executor(self, spec: CircuitSpec):
        if spec not in self._per_spec:
            self._per_spec[spec] = sharded_executor(spec, self.mesh, self.axis)
        return self._per_spec[spec]

    def rows(self, spec: CircuitSpec, theta_bank, data_bank):
        """(C, P), (C, D) -> (C,) fidelities, lanes sharded over the mesh."""
        return self._executor(spec)(theta_bank, data_bank)

    def banks(
        self,
        spec: CircuitSpec,
        thetas,
        datas,
        four_term: bool,
        group_sets: tuple,
    ):
        """Fused multi-bank bank-set execution sharded over the mesh."""
        return self._executor(spec).run_banks(thetas, datas, four_term, group_sets)


def bank_shardings(mesh: Mesh, axis: str = "data"):
    """in_shardings for (theta_bank, data_bank) under pjit."""
    s = NamedSharding(mesh, P(axis, None))
    return (s, s)
