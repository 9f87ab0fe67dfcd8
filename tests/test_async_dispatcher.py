"""Async serving runtime (repro.serve.async_dispatcher): out-of-order future
resolution, backpressure under a saturated worker pool, priority preemption
of the stride scheduler, SLO deadline-miss accounting, the EWMA service-time
cost model, and the determinism guarantee (async results bit-identical to
the sync dispatcher on the same submissions)."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.comanager.worker import WorkerConfig
from repro.core.quclassi import QuClassiConfig
from repro.kernels import ops as kops
from repro.serve import (
    Backpressure,
    CoalescedBatch,
    Gateway,
    GatewayRuntime,
    PendingCircuit,
    ServiceModel,
    batch_cost_units,
)


def wait_until(pred, timeout=10.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.005)
    return False


@pytest.fixture(scope="module")
def specs():
    cfg5 = QuClassiConfig(qc=5, n_layers=1)
    cfg7 = QuClassiConfig(qc=7, n_layers=1)
    return cfg5, cfg7


def rows_for(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.uniform(0, np.pi, (n, cfg.n_theta)), jnp.float32)
    data = jnp.asarray(rng.uniform(0, np.pi, (n, cfg.n_angles)), jnp.float32)
    return theta, data


def gated_kernel(block_widths, gate: threading.Event):
    """A kernel that stalls batches of the given qubit widths on ``gate``."""

    def kernel(spec, theta, data):
        if spec.n_qubits in block_widths:
            assert gate.wait(timeout=30.0), "test gate never released"
        return kops.vqc_fidelity(spec, theta, data)

    return kernel


# ------------------------------------------------- out-of-order resolution
def test_futures_resolve_out_of_order(specs):
    """A stalled mega-batch on one worker must not block another tenant's
    batch executing on a different worker slot: the later submission's
    futures resolve first."""
    cfg5, cfg7 = specs
    gate = threading.Event()
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5), WorkerConfig("w2", 10)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode="async",
        kernel=gated_kernel({5}, gate),
    )
    try:
        t5, d5 = rows_for(cfg5, 8)
        t7, d7 = rows_for(cfg7, 8)
        now = rt.dispatcher.clock
        slow = [
            rt.gateway.submit("tenant", cfg5.spec, (t5[i], d5[i]), now())
            for i in range(8)
        ]
        fast = [
            rt.gateway.submit("tenant", cfg7.spec, (t7[i], d7[i]), now())
            for i in range(8)
        ]
        rt.dispatcher.kick()
        for f in fast:
            f.result(timeout=30.0)
        assert not any(f.done for f in slow), "stalled batch resolved early"
        gate.set()
        for f in slow:
            f.result(timeout=30.0)
        ref = kops.vqc_fidelity(cfg5.spec, t5, d5)
        got = jnp.stack([f.value for f in slow])
        assert np.array_equal(np.asarray(ref), np.asarray(got))
    finally:
        gate.set()
        rt.close()


# --------------------------------------------- backpressure under saturation
def test_backpressure_when_worker_pool_saturated(specs):
    """With the single worker slot stalled and the tenant at its in-flight
    cap, the admission queue fills and submit raises Backpressure; releasing
    the pool drains everything."""
    cfg5, _ = specs
    gate = threading.Event()
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=4,
        lanes=4,
        deadline=10.0,
        mode="async",
        kernel=gated_kernel({5}, gate),
    )
    try:
        rt.gateway.register_client("t", max_pending=4, max_in_flight=4)
        theta, data = rows_for(cfg5, 9)
        now = rt.dispatcher.clock
        futs = [
            rt.gateway.submit("t", cfg5.spec, (theta[i], data[i]), now())
            for i in range(4)
        ]
        rt.dispatcher.kick()
        assert wait_until(lambda: rt.dispatcher.in_flight_batches == 1)
        futs += [
            rt.gateway.submit("t", cfg5.spec, (theta[i], data[i]), now())
            for i in range(4, 8)
        ]
        with pytest.raises(Backpressure):
            rt.gateway.submit("t", cfg5.spec, (theta[8], data[8]), now())
        assert rt.telemetry.tenants["t"].rejected == 1
        gate.set()
        rt.dispatcher.drain()
        assert all(f.done for f in futs)
    finally:
        gate.set()
        rt.close()


# --------------------------------------------------- priority tier preemption
def test_priority_tier_preempts_stride_scheduling():
    """A tier-0 tenant joining late is served strictly before tier-1 backlog
    regardless of accumulated virtual passes."""
    g = Gateway(target=128, lanes=128, deadline=100.0)
    g.register_client("batch", weight=10.0, priority=1)
    for i in range(20):
        g.submit("batch", "k", i, now=0.0)
    g.pump(now=0.0)  # batch's vpass advances well past 0
    g.register_client("interactive", priority=0)
    for i in range(5):
        g.submit("interactive", "k", 100 + i, now=1.0)
        g.submit("batch", "k", 200 + i, now=1.0)
    g.pump(now=1.0)
    tail = [m.client_id for m in g.coalescer._buffers["k"]][20:]
    assert tail[:5] == ["interactive"] * 5
    assert tail[5:] == ["batch"] * 5


def test_priority_preemption_through_async_runtime(specs):
    """End to end: with one stalled slot, a tier-0 tenant's circuits jump the
    tier-1 backlog when the slot frees."""
    cfg5, _ = specs
    gate = threading.Event()
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=4,
        lanes=4,
        deadline=10.0,
        mode="async",
        kernel=gated_kernel({5}, gate),
    )
    try:
        rt.gateway.register_client("bulk", priority=1, max_in_flight=4)
        rt.gateway.register_client("vip", priority=0)
        theta, data = rows_for(cfg5, 12)
        now = rt.dispatcher.clock
        bulk = [
            rt.gateway.submit("bulk", cfg5.spec, (theta[i], data[i]), now())
            for i in range(8)
        ]
        rt.dispatcher.kick()
        assert wait_until(lambda: rt.dispatcher.in_flight_batches == 1)
        vip = [
            rt.gateway.submit("vip", cfg5.spec, (theta[i], data[i]), now())
            for i in range(8, 12)
        ]
        rt.dispatcher.kick()
        gate.set()
        rt.dispatcher.drain()
        assert all(f.done for f in bulk) and all(f.done for f in vip)
        # batch 1 = bulk's first four (already in flight before vip joined);
        # batch 2 must be all-vip: the tier-0 queue preempted bulk's backlog.
        second = rt.dispatcher.batch_log[1]
        assert second[2] == ("vip",)
    finally:
        gate.set()
        rt.close()


# ------------------------------------------------------- SLO deadline misses
def test_slo_flush_deadline_shortens_coalescer_wait():
    """A tenant SLO shrinks the flush deadline to half the SLO budget."""
    g = Gateway(target=128, lanes=128, deadline=10.0)
    g.register_client("fast", slo_ms=100.0)
    g.register_client("easy")
    g.submit("easy", "k", 0, now=0.0)
    g.pump(now=0.0)
    assert g.next_deadline() == pytest.approx(10.0)  # default deadline
    g.submit("fast", "k", 1, now=0.0)
    g.pump(now=0.0)
    # min over members: the SLO tenant pulls the shared buffer forward
    assert g.next_deadline() == pytest.approx(0.05)
    assert g.pump(now=0.04) == []
    (batch,) = g.pump(now=0.05)
    assert batch.by_deadline and batch.n == 2


def test_slo_miss_accounting(specs):
    """Completions past the SLO are counted per tenant; attainment reported."""
    cfg5, _ = specs

    def slow_kernel(spec, theta, data):
        time.sleep(0.05)
        return kops.vqc_fidelity(spec, theta, data)

    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5), WorkerConfig("w2", 5)],
        target=4,
        lanes=4,
        deadline=0.01,
        mode="async",
        kernel=slow_kernel,
    )
    try:
        theta, data = rows_for(cfg5, 8)
        ex_tight = rt.executor(cfg5.spec, "tight", slo_ms=1.0)
        ex_loose = rt.executor(cfg5.spec, "loose", slo_ms=60_000.0)
        ex_tight(theta[:4], data[:4])
        ex_loose(theta[4:], data[4:])
        tight = rt.telemetry.tenants["tight"]
        loose = rt.telemetry.tenants["loose"]
        assert tight.slo_misses == tight.completed == 4
        assert tight.slo_attainment == 0.0
        assert loose.slo_misses == 0 and loose.slo_attainment == 1.0
        summary = rt.telemetry.summary()
        assert summary["slo_misses"] == 4
        assert 0.0 < summary["slo_attainment"] < 1.0
    finally:
        rt.close()


# ----------------------------------------------------- EWMA service estimates
def test_service_model_ewma_converges():
    m = ServiceModel(alpha=0.5, default_s=1.0)
    assert m.estimate("k", 100.0) == 1.0  # no observations: default
    m.update("k", 100.0, 2.0)  # 0.02 s/unit
    assert m.estimate("k", 100.0) == pytest.approx(2.0)
    m.update("k", 100.0, 4.0)  # ewma: 0.5*0.04 + 0.5*0.02
    assert m.estimate("k", 100.0) == pytest.approx(3.0)
    # unseen keys fall back to the global ewma, not the flat default
    assert m.estimate("other", 100.0) == pytest.approx(3.0)


def test_batch_cost_units_scale_with_lanes_and_suffix(specs):
    cfg5, _ = specs
    spec = cfg5.spec

    def row_batch(n):
        members = [
            PendingCircuit(key=spec, client_id="c", seq=i, arrival=0.0, payload=None)
            for i in range(n)
        ]
        return CoalescedBatch(key=spec, members=members, created=0.0)

    small, large = batch_cost_units(row_batch(8)), batch_cost_units(row_batch(200))
    # 8 rows pad to one 128-lane tile, 200 rows to two
    assert large == pytest.approx(2 * small)
    assert small == len(spec.ops) * 128


def test_ewma_feeds_worker_cru(specs):
    """Predicted service seconds are charged to the assigned worker's CRU
    while a batch is outstanding, steering Algorithm 2 elsewhere."""
    cfg5, _ = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5), WorkerConfig("w2", 5)],
        target=4,
        lanes=4,
        deadline=10.0,
    )
    try:
        d = rt.dispatcher
        d._charge("w1", 2.5)
        assert d.manager.workers["w1"].cru == pytest.approx(2.5)
        assert d.manager.workers["w2"].cru == 0.0
        theta, data = rows_for(cfg5, 4)
        now = d.clock
        for i in range(4):
            rt.gateway.submit("c", cfg5.spec, (theta[i], data[i]), now())
        d.drain()
        # the charged worker lost the CRU tiebreak: the batch ran on w2
        assert d.batch_log[0][0] == "w2"
        d._charge("w1", -2.5)
        assert d.manager.workers["w1"].cru == pytest.approx(0.0)
        # execution updated the EWMA: estimates are no longer the default
        est = rt.telemetry.service.estimate(cfg5.spec, 1.0)
        assert 0.0 < est < 1.0
    finally:
        rt.close()


# ------------------------------------------------------ determinism / safety
def test_async_results_bit_identical_to_sync(specs):
    """Acceptance: the async dispatcher returns bit-identical fidelities to
    the sync dispatcher on the same submissions (batch composition never
    changes per-lane math)."""
    cfg5, _ = specs
    theta, data = rows_for(cfg5, 70)
    rt_sync = GatewayRuntime(target=128, deadline=0.1)
    f_sync = rt_sync.executor(cfg5.spec, "c")(theta, data)
    rt_async = GatewayRuntime(
        target=128, deadline=0.1, mode="async", slots_per_worker=2
    )
    try:
        f_async = rt_async.executor(cfg5.spec, "c")(theta, data)
    finally:
        rt_async.close()
    assert np.array_equal(np.asarray(f_sync), np.asarray(f_async))


def test_async_shift_executor_matches_local_gradient(specs):
    """Implicit shift-bank group subtasks ride the async path too, and the
    assembled gradient matches the local executor."""
    from repro.core import quclassi

    cfg5, _ = specs
    import jax

    from repro.data import mnist

    x, y = mnist.make_pair_dataset(3, 9, n_per_class=4, seed=0)
    x, y = jnp.asarray(x[:2]), jnp.asarray(y[:2])
    params = quclassi.init_params(cfg5, jax.random.PRNGKey(0))
    l_ref, g_ref, _ = quclassi.grad_shift(cfg5, params, x, y, implicit=True)
    rt = GatewayRuntime(target=128, deadline=0.2, mode="async")
    try:
        ex = rt.shift_executor(cfg5.spec, "t1")
        l_gw, g_gw, _ = quclassi.grad_shift(
            cfg5, params, x, y, executor=ex, implicit=True
        )
    finally:
        rt.close()
    assert float(l_gw) == pytest.approx(float(l_ref), abs=1e-6)
    np.testing.assert_allclose(
        np.asarray(g_gw["theta"]), np.asarray(g_ref["theta"]), atol=1e-5
    )


def test_concurrent_submitters_do_not_corrupt_state(specs):
    """Satellite: user threads hammering submit while the pump and worker
    pool run — per-thread results stay correct and counts balance."""
    cfg5, _ = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5), WorkerConfig("w2", 10)],
        target=32,
        lanes=32,
        deadline=0.02,
        mode="async",
    )
    results = {}

    def client(tid):
        theta, data = rows_for(cfg5, 40, seed=tid)
        ex = rt.executor(cfg5.spec, f"c{tid}")
        results[tid] = (np.asarray(ex(theta, data)), theta, data)

    try:
        threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
        assert not any(t.is_alive() for t in threads)
        assert not rt.dispatcher.errors
        for tid, (got, theta, data) in results.items():
            ref = np.asarray(kops.vqc_fidelity(cfg5.spec, theta, data))
            np.testing.assert_array_equal(got, ref)
        for tid in range(4):
            s = rt.telemetry.tenants[f"c{tid}"]
            assert s.completed == s.submitted == 40
    finally:
        rt.close()


def test_drain_surfaces_pump_loop_errors(specs):
    """A wedged pump loop must fail drain() with its error, not hang it."""
    cfg5, _ = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)], target=4, lanes=4, mode="async"
    )
    try:

        def boom():
            raise ValueError("pump exploded")

        rt.dispatcher._pump_once = boom
        rt.dispatcher.kick()
        assert wait_until(lambda: rt.dispatcher.errors)
        with pytest.raises(ValueError, match="pump exploded"):
            rt.dispatcher.drain()
    finally:
        rt.close()


def test_worker_pool_executor_matches_sequential(specs):
    """The thread-pooled dataplane executor returns bank-order results
    bit-identical to the sequential per-worker executor, for materialized
    rows and implicit shift banks alike."""
    from repro.comanager import dataplane
    from repro.core import shift_rule

    cfg5, _ = specs
    theta, data = rows_for(cfg5, 30)
    assignment = dataplane.round_robin_assignment(30, 3)
    f_seq = dataplane.worker_batched_executor(cfg5.spec, assignment, 3)(theta, data)
    f_pool = dataplane.worker_pool_executor(cfg5.spec, assignment, 3)(theta, data)
    assert np.array_equal(np.asarray(f_seq), np.asarray(f_pool))

    bank = shift_rule.build_shift_bank(theta[0], data[:4])
    groups = dataplane.round_robin_assignment(bank.n_groups, 3)
    g_seq = dataplane.worker_batched_executor(cfg5.spec, groups, 3)(bank)
    g_pool = dataplane.worker_pool_executor(cfg5.spec, groups, 3)(bank)
    assert np.array_equal(np.asarray(g_seq), np.asarray(g_pool))


def test_oversized_batch_spills_to_mesh(specs):
    """A batch wider than every worker no longer fails fast: it routes
    through the whole-mesh sharded executor, completes with correct
    fidelities, and the spill is visible in telemetry."""
    _, cfg7 = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=4,
        lanes=4,
        deadline=0.01,
        mode="async",
    )
    try:
        theta, data = rows_for(cfg7, 2)
        futs = [
            rt.gateway.submit(
                "c", cfg7.spec, (theta[i], data[i]), rt.dispatcher.clock()
            )
            for i in range(2)
        ]
        rt.dispatcher.kick()
        got = np.asarray([np.asarray(f.result(timeout=60.0)) for f in futs])
        ref = np.asarray(kops.vqc_fidelity(cfg7.spec, theta, data))
        np.testing.assert_allclose(got, ref, atol=1e-6)
        assert rt.telemetry.mesh_spills >= 1
        assert rt.telemetry.spilled_lanes >= 2
        assert any(wid == "mesh" for wid, _, _ in rt.dispatcher.batch_log)
        assert not rt.dispatcher.errors
    finally:
        rt.close()


class _BrokenMesh:
    """A whole-mesh spill executor whose every launch raises."""

    def rows(self, spec, theta, data):
        raise RuntimeError("mesh launch failed")

    def banks(self, spec, thetas, datas, four_term, group_sets):
        raise RuntimeError("mesh launch failed")


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_spill_failure_fails_futures(specs, mode):
    """A whole-mesh launch that raises resolves every future of the
    spilled batch with its error, in both drivers; none is left hanging.
    The sync driver also raises it from drain()."""
    _, cfg7 = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=4,
        lanes=4,
        deadline=0.01,
        mode=mode,
        spill_executor=_BrokenMesh(),
    )
    try:
        theta, data = rows_for(cfg7, 3)
        futs = [
            rt.gateway.submit(
                "c", cfg7.spec, (theta[i], data[i]), rt.dispatcher.clock()
            )
            for i in range(3)
        ]
        if mode == "sync":
            with pytest.raises(RuntimeError, match="mesh launch failed"):
                rt.dispatcher.drain()
        else:
            rt.dispatcher.drain()
            assert any("mesh launch failed" in str(e) for e in rt.dispatcher.errors)
        for f in futs:
            with pytest.raises(RuntimeError, match="mesh launch failed"):
                f.result(timeout=10.0)
        log = rt.dispatcher.batch_log
        assert log and all(wid == "mesh" for wid, _, _ in log)
    finally:
        rt.close()


def test_oversized_batch_fails_fast_when_spill_disabled(specs):
    """mesh_spill=False restores the strict contract: futures resolve with
    the placement error instead of deadlocking the pump."""
    _, cfg7 = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=4,
        lanes=4,
        deadline=0.01,
        mode="async",
        mesh_spill=False,
    )
    try:
        theta, data = rows_for(cfg7, 1)
        fut = rt.gateway.submit(
            "c", cfg7.spec, (theta[0], data[0]), rt.dispatcher.clock()
        )
        rt.dispatcher.kick()
        with pytest.raises(RuntimeError, match="no worker fits"):
            fut.result(timeout=10.0)
    finally:
        rt.close()


def test_sync_dispatcher_spills_oversized_batches(specs):
    """The sync dispatcher spills too: an over-width bank executes on the
    mesh inline with bit-correct results."""
    from repro.core import shift_rule

    _, cfg7 = specs
    rt = GatewayRuntime(workers=[WorkerConfig("w1", 5)], deadline=0.01)
    try:
        theta, data = rows_for(cfg7, 3)
        bank = shift_rule.build_shift_bank(theta[0], data)
        got = rt.shift_executor(cfg7.spec, "c")(bank)
        want = kops.vqc_fidelity_shiftbank(cfg7.spec, bank.theta, bank.data)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
        assert rt.telemetry.mesh_spills >= 1
        assert rt.dispatcher.batch_log[0][0] == "mesh"
    finally:
        rt.close()


def test_vmem_model_flags_deep_row_batches():
    """batch_vmem_bytes: a 17-qubit row batch's statevector tile blows the
    16 MB per-worker model (spill), a 7-qubit one does not."""
    from repro.core import circuits
    from repro.serve import WORKER_VMEM_BYTES, batch_vmem_bytes

    def row_batch(spec, n):
        members = [
            PendingCircuit(key=spec, client_id="c", seq=i, arrival=0.0, payload=None)
            for i in range(n)
        ]
        return CoalescedBatch(key=spec, members=members, created=0.0)

    wide = circuits.build_quclassi_circuit(17, 1)
    assert batch_vmem_bytes(row_batch(wide, 8)) > WORKER_VMEM_BYTES
    narrow = circuits.build_quclassi_circuit(7, 1)
    assert batch_vmem_bytes(row_batch(narrow, 8)) <= WORKER_VMEM_BYTES


# ------------------------------------------------- preemptive SLO eviction
def test_over_slo_batches_preemptively_evicted(specs):
    """With evict_over_slo on, a ready batch whose members' SLO budgets
    fully elapsed behind a stalled worker resolves with DeadlineExceeded
    and is accounted (evicted + slo miss), instead of burning a slot on a
    guaranteed miss."""
    from repro.serve import DeadlineExceeded

    cfg5, _ = specs
    gate = threading.Event()
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=2,
        lanes=2,
        deadline=0.01,
        mode="async",
        evict_over_slo=True,
        kernel=gated_kernel({5}, gate),
    )
    try:
        rt.gateway.register_client("t", slo_ms=150.0)
        theta, data = rows_for(cfg5, 4)
        now = rt.dispatcher.clock
        first = [
            rt.gateway.submit("t", cfg5.spec, (theta[i], data[i]), now())
            for i in range(2)
        ]
        rt.dispatcher.kick()
        assert wait_until(lambda: rt.dispatcher.in_flight_batches == 1)
        # second batch can only wait in the ready queue (slot is stalled);
        # its 150 ms SLO budget fully elapses -> preemptive eviction
        second = [
            rt.gateway.submit("t", cfg5.spec, (theta[i], data[i]), now())
            for i in range(2, 4)
        ]
        rt.dispatcher.kick()
        assert wait_until(
            lambda: rt.telemetry.tenants["t"].evicted == 2, timeout=30.0
        )
        for f in second:
            with pytest.raises(DeadlineExceeded):
                f.result(timeout=10.0)
        gate.set()
        rt.dispatcher.drain()
        stats = rt.telemetry.tenants["t"]
        assert stats.evicted == 2
        assert stats.slo_misses >= 2          # evictions count as misses
        assert stats.completed == 2           # first batch still completed
        assert stats.slo_attainment <= 0.5
        assert rt.telemetry.summary()["evicted"] == 2
        assert all(f.done for f in first)
    finally:
        gate.set()
        rt.close()


def test_eviction_spares_batches_with_best_effort_members():
    """A mixed batch containing a best-effort member is never evicted —
    that member's result is still wanted whenever it arrives."""
    from repro.serve.async_dispatcher import AsyncDispatcher
    from repro.serve.coalescer import CoalescedBatch as CB

    g = Gateway(target=4, lanes=4, deadline=10.0)
    g.register_client("slo", slo_ms=10.0)
    g.register_client("be")
    d = AsyncDispatcher(g, [WorkerConfig("w1", 5)], evict_over_slo=True)
    try:
        g.submit("slo", "k", None, now=0.0)
        g.submit("be", "k", None, now=0.0)
        (batch,) = g.flush(now=0.0)
        assert not d._expired(batch, now=100.0)        # best-effort member
        slo_m = next(m for m in batch.members if m.client_id == "slo")
        slo_only = CB(key="k", members=[slo_m], created=0.0)
        assert d._expired(slo_only, now=100.0)
        assert not d._expired(slo_only, now=0.005)     # within budget
    finally:
        d.close()


# ------------------------------------- mixed-bank SLO-aware deadline flush
def test_mixed_slo_bank_buffer_flushes_at_min_member_budget(specs):
    """Deterministic (virtual-clock) half: a shared ShiftGroupKey buffer
    holding group subtasks of banks with DIFFERENT slo_ms flushes at the
    MIN member budget — the tight tenant pulls the loose tenant's bank
    forward with it."""
    from repro.core import shift_rule
    from repro.serve import ShiftGroupKey

    cfg5, _ = specs
    g = Gateway(target=128, lanes=128, deadline=10.0)
    g.register_client("tight", slo_ms=500.0)
    g.register_client("loose", slo_ms=60_000.0)
    theta, data = rows_for(cfg5, 8)
    bank_a = shift_rule.build_shift_bank(theta[0], data[:4])
    bank_b = shift_rule.build_shift_bank(theta[1], data[4:])
    key = ShiftGroupKey(cfg5.spec, False)
    for grp in range(bank_b.n_groups):
        g.submit("loose", key, (bank_b, grp), now=0.0, lanes=4)
    assert g.pump(now=0.0) == []
    # loose alone: flush at min(deadline, 0.5 * 60 s) = the 10 s deadline
    assert g.next_deadline() == pytest.approx(10.0)
    for grp in range(bank_a.n_groups):
        g.submit("tight", key, (bank_a, grp), now=0.0, lanes=4)
    assert g.pump(now=0.0) == []
    # tight joins the SAME buffer: min member budget = 0.5 * 0.5 s
    assert g.next_deadline() == pytest.approx(0.25)
    assert g.pump(now=0.2) == []
    (batch,) = g.pump(now=0.25)
    assert batch.by_deadline
    assert batch.n == bank_a.n_groups + bank_b.n_groups
    assert {m.client_id for m in batch.members} == {"tight", "loose"}


def test_mixed_slo_banks_stay_bit_exact_after_fusion(specs):
    """Real-execution half: the mixed-SLO shared buffer fuses into
    multi-bank launches through the async runtime and every fidelity is
    bit-identical to the per-bank implicit path."""
    from repro.core import shift_rule
    from repro.serve import ShiftGroupKey

    cfg5, _ = specs
    spec = cfg5.spec
    rt = GatewayRuntime(deadline=0.2, mode="async")
    try:
        rt.gateway.register_client("tight", slo_ms=500.0)
        rt.gateway.register_client("loose", slo_ms=60_000.0)
        theta, data = rows_for(cfg5, 8)
        bank_a = shift_rule.build_shift_bank(theta[0], data[:4])
        bank_b = shift_rule.build_shift_bank(theta[1], data[4:])
        key = ShiftGroupKey(spec, False)
        now = rt.dispatcher.clock
        futs_a = [
            rt.gateway.submit("tight", key, (bank_a, g), now(), lanes=4)
            for g in range(bank_a.n_groups)
        ]
        futs_b = [
            rt.gateway.submit("loose", key, (bank_b, g), now(), lanes=4)
            for g in range(bank_b.n_groups)
        ]
        rt.dispatcher.kick()
        got_a = jnp.concatenate([f.result(timeout=30.0) for f in futs_a])
        got_b = jnp.concatenate([f.result(timeout=30.0) for f in futs_b])
        want_a = kops.vqc_fidelity_shiftbank(spec, bank_a.theta, bank_a.data)
        want_b = kops.vqc_fidelity_shiftbank(spec, bank_b.theta, bank_b.data)
        assert np.array_equal(np.asarray(got_a), np.asarray(want_a))
        assert np.array_equal(np.asarray(got_b), np.asarray(want_b))
        assert rt.telemetry.fused_launches >= 1
        assert rt.telemetry.fused_banks >= 2
    finally:
        rt.close()


def test_partial_group_batches_run_the_whole_bank_program(specs):
    """A batch that carries only some groups of its banks runs the program
    of whole banks and returns exactly their rows: how the coalescer split
    concurrent banks between batches compiles nothing new."""
    from repro.core import shift_rule
    from repro.serve import ShiftGroupKey, execute_batch

    cfg5, _ = specs
    theta, data = rows_for(cfg5, 8)
    banks = [
        shift_rule.build_shift_bank(theta[k], data[4 * k : 4 * k + 4])
        for k in range(2)
    ]
    key = ShiftGroupKey(cfg5.spec, False)
    n = banks[0].n_groups

    def run(groups_of):
        """Execute one batch holding groups ``groups_of[k]`` of bank k."""
        members = [
            PendingCircuit(key, "t", 0, 0.0, (banks[k], g), lanes=4)
            for k, gs in enumerate(groups_of)
            for g in gs
        ]
        batch = CoalescedBatch(key=key, members=members, created=0.0)
        return [np.asarray(r) for r in execute_batch(
            batch, kops.vqc_fidelity, kops.vqc_fidelity_shiftgroups
        )]

    def programs():
        return (kops._shiftgroups_jit._cache_size(),
                kops._shiftgroups_multibank_jit._cache_size())

    one, two = run([range(n)]), run([range(n), range(n)])
    before = programs()
    part_one = run([(0, 3, n - 1)])
    part_two = run([(1, 2), (4, n - 1)])
    assert programs() == before
    for got, g in zip(part_one, (0, 3, n - 1)):
        assert np.array_equal(got, one[g])
    for got, (k, g) in zip(part_two, ((0, 1), (0, 2), (1, 4), (1, n - 1))):
        assert np.array_equal(got, two[k * n + g])


@pytest.mark.parametrize("host", [True, False], ids=["numpy_rows", "device_rows"])
def test_row_batches_of_one_bucket_share_their_programs(specs, host):
    """Row batches of any member count within one lane bucket run the
    programs of the first (deadline flushes emit arbitrary counts; a new
    program per count would compile on the dispatch path), and each member
    gets the fidelity of its own row."""
    import jax

    from repro.serve import execute_batch

    cfg5, _ = specs
    theta, data = rows_for(cfg5, 9, seed=3)
    if host:
        theta, data = np.asarray(theta), np.asarray(data)

    def run(n):
        members = [
            PendingCircuit(cfg5.spec, "t", i, 0.0, (theta[i], data[i])) for i in range(n)
        ]
        batch = CoalescedBatch(key=cfg5.spec, members=members, created=0.0)
        return [np.asarray(f) for f in execute_batch(
            batch, kops.vqc_fidelity, kops.vqc_fidelity_shiftgroups
        )]

    compiles = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.clear_caches()
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        run(3)
        before = len(compiles)
        got = run(7)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert compiles[before:] == []
    want = np.asarray(kops.vqc_fidelity(cfg5.spec, jnp.asarray(theta[:7]), jnp.asarray(data[:7])))
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=1e-6)
