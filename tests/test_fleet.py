"""Elastic fleet (repro.serve.fleet + fault-tolerant dispatch): worker
health state machine and circuit breaker, typed fault schedules, retry /
migration with bit-identical replay on both dispatchers, hedged dispatch
with first-result-wins, live membership (register/drain at runtime), and
the virtual-clock simulation's mirrored fault kinds."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.comanager.faults import (
    FaultSpec,
    FaultToleranceConfig,
    normalize_failures,
)
from repro.comanager.simulation import SystemSimulation, homogeneous_workers
from repro.comanager.tenancy import JobSpec
from repro.comanager.worker import WorkerConfig
from repro.core.quclassi import QuClassiConfig
from repro.kernels import ops as kops
from repro.serve import GatewayRuntime
from repro.serve.fleet import FaultInjector, FleetHealth, InjectedWorkerFault


def wait_until(pred, timeout=10.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.005)
    return False


@pytest.fixture(scope="module")
def specs():
    return QuClassiConfig(qc=5, n_layers=1), QuClassiConfig(qc=7, n_layers=1)


def rows_for(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    theta = jnp.asarray(rng.uniform(0, np.pi, (n, cfg.n_theta)), jnp.float32)
    data = jnp.asarray(rng.uniform(0, np.pi, (n, cfg.n_angles)), jnp.float32)
    return theta, data


def two_jobs():
    return [
        JobSpec("alice", n_circuits=30, qc=5, n_layers=1, submit_time=0.0),
        JobSpec("bob", n_circuits=30, qc=5, n_layers=1, submit_time=0.0),
    ]


# ---------------------------------------------------- health state machine
class TestFleetHealth:
    def mk(self, **kw):
        fleet = FleetHealth(FaultToleranceConfig(**kw))
        fleet.add("w1")
        return fleet

    def test_breaker_trips_after_consecutive_failures(self):
        fleet = self.mk(breaker_threshold=3, breaker_cooldown_s=5.0)
        assert not fleet.on_failure("w1", 0.0)
        assert not fleet.on_failure("w1", 0.1)
        assert fleet.on_failure("w1", 0.2)  # third strike trips
        assert fleet.state("w1") == "offline"
        assert not fleet.placeable("w1", 1.0)
        assert "w1" in fleet.unplaceable(1.0)

    def test_success_resets_consecutive_count(self):
        fleet = self.mk(breaker_threshold=2)
        fleet.on_failure("w1", 0.0)
        fleet.on_success("w1")
        assert not fleet.on_failure("w1", 0.1)  # count restarted
        assert fleet.state("w1") != "offline"

    def test_cooldown_half_opens_to_probation(self):
        fleet = self.mk(breaker_threshold=1, breaker_cooldown_s=2.0)
        fleet.on_failure("w1", 0.0)
        assert not fleet.placeable("w1", 1.0)
        assert fleet.placeable("w1", 2.5)  # half-open trial
        assert fleet.state("w1") == "probation"

    def test_probation_failure_retrips_immediately(self):
        fleet = self.mk(breaker_threshold=3, breaker_cooldown_s=2.0)
        for i in range(3):
            fleet.on_failure("w1", i * 0.1)
        assert fleet.placeable("w1", 3.0)
        assert fleet.on_failure("w1", 3.1)  # one probation strike re-trips
        assert fleet.state("w1") == "offline"
        assert fleet.snapshot()["w1"]["offline_trips"] == 2

    def test_probation_success_closes_breaker(self):
        fleet = self.mk(breaker_threshold=1, breaker_cooldown_s=1.0)
        fleet.on_failure("w1", 0.0)
        assert fleet.placeable("w1", 2.0)
        fleet.on_success("w1")
        assert fleet.state("w1") in ("idle", "busy")
        assert fleet.snapshot()["w1"]["consecutive_errors"] == 0

    def test_failure_rate_is_ewma(self):
        fleet = self.mk(failure_alpha=0.5, breaker_threshold=100)
        fleet.on_failure("w1", 0.0)
        assert fleet.snapshot()["w1"]["failure_rate"] == pytest.approx(0.5)
        fleet.on_success("w1")
        assert fleet.snapshot()["w1"]["failure_rate"] == pytest.approx(0.25)

    def test_draining_not_placeable_and_never_trips(self):
        fleet = self.mk(breaker_threshold=1)
        fleet.mark_draining("w1")
        assert not fleet.placeable("w1", 0.0)
        assert not fleet.on_failure("w1", 0.0)  # drain beats breaker
        assert fleet.state("w1") == "draining"

    def test_maintenance_and_reactivate(self):
        fleet = self.mk()
        fleet.mark_maintenance("w1")
        assert not fleet.placeable("w1", 0.0)
        fleet.reactivate("w1")
        assert fleet.placeable("w1", 0.0)

    def test_busy_slot_accounting(self):
        fleet = self.mk()
        fleet.on_dispatch("w1")
        assert fleet.state("w1") == "busy"
        fleet.on_release("w1")
        assert fleet.state("w1") == "idle"

    def test_snapshot_counters(self):
        fleet = self.mk(breaker_threshold=2)
        fleet.on_dispatch("w1")
        fleet.on_failure("w1", 0.0)
        fleet.record_retry("w1")
        fleet.record_migration("w1")
        fleet.record_hedge("w1")
        snap = fleet.snapshot()["w1"]
        assert snap["failures"] == 1
        assert snap["retries"] == 1
        assert snap["migrations"] == 1
        assert snap["hedges"] == 1
        assert snap["state"] == "busy"


# ------------------------------------------------- fault-schedule validation
class TestFaultSchedules:
    @pytest.mark.parametrize(
        "bad",
        [
            {"kind": "nope"},
            {"at": -1.0},
            {"at": float("nan")},
            {"kind": "crash_recover", "at": 5.0, "recover_at": 2.0},
            {"kind": "crash_recover", "at": 5.0, "recover_at": float("inf")},
            {"kind": "slowdown", "factor": 0.0},
            {"kind": "flaky", "p": 1.5},
            "never",
        ],
    )
    def test_invalid_specs_name_the_worker(self, bad):
        with pytest.raises(ValueError, match="w1"):
            normalize_failures({"w1": bad})

    def test_legacy_float_still_means_crash(self):
        spec = normalize_failures({"w1": 3.5})["w1"]
        assert spec.kind == "crash" and spec.at == 3.5
        assert not spec.crashed(3.0) and spec.crashed(4.0)

    def test_crash_recover_window(self):
        spec = FaultSpec(kind="crash_recover", at=2.0, recover_at=5.0)
        assert not spec.crashed(1.0)
        assert spec.crashed(2.0) and spec.crashed(4.9)
        assert not spec.crashed(5.0)
        assert spec.crashed_between(1.0, 3.0)
        assert not spec.crashed_between(5.0, 9.0)

    def test_flaky_drops_deterministic_and_retries_progress(self):
        spec = FaultSpec(kind="flaky", p=0.5, seed=7)
        draws = [spec.drops(11, k, 0.0) for k in range(64)]
        assert draws == [spec.drops(11, k, 0.0) for k in range(64)]
        assert any(draws) and not all(draws)  # retries eventually pass

    def test_simulation_rejects_bad_schedule_at_construction(self):
        with pytest.raises(ValueError, match="w1"):
            SystemSimulation(
                homogeneous_workers(2, 10),
                two_jobs(),
                worker_failures={"w1": {"kind": "flaky", "p": -0.1}},
            )


# ------------------------------------------- real dispatchers: crash replay
def crash_runtime(specs, mode, **ft_kw):
    """Two-worker runtime with w1 hard-crashed from t=0: every batch placed
    on (or retried against) w1 fails, trips its breaker, and must migrate
    to w2 through the coalescer requeue path."""
    cfg5, cfg7 = specs
    ft = FaultToleranceConfig(
        retry_limit=0, breaker_threshold=1, breaker_cooldown_s=3600.0, **ft_kw
    )
    inj = FaultInjector({"w1": FaultSpec(kind="crash", at=0.0)})
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 10), WorkerConfig("w2", 10)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode=mode,
        fault_tolerance=ft,
        fault_injector=inj,
    )
    return rt


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_crash_migration_is_bit_identical(specs, mode):
    """The headline replay guarantee: a mid-batch worker crash migrates the
    batch to a survivor and every CircuitFuture resolves to exactly the
    value a fault-free run produces — no lost futures, no duplicates."""
    cfg5, cfg7 = specs
    rt = crash_runtime(specs, mode)
    try:
        t5, d5 = rows_for(cfg5, 8, seed=1)
        t7, d7 = rows_for(cfg7, 8, seed=2)
        now = rt.dispatcher.clock
        futs5 = [
            rt.gateway.submit("alice", cfg5.spec, (t5[i], d5[i]), now())
            for i in range(8)
        ]
        futs7 = [
            rt.gateway.submit("bob", cfg7.spec, (t7[i], d7[i]), now())
            for i in range(8)
        ]
        if mode == "sync":
            rt.dispatcher.drain()
        else:
            rt.dispatcher.kick()
        vals5 = [f.result(timeout=60.0) for f in futs5]
        vals7 = [f.result(timeout=60.0) for f in futs7]
        assert all(f.done for f in futs5 + futs7)
        # bit-identical to the fault-free reference, in submission order
        ref5 = np.asarray(kops.vqc_fidelity(cfg5.spec, t5, d5))
        ref7 = np.asarray(kops.vqc_fidelity(cfg7.spec, t7, d7))
        assert np.array_equal(np.asarray(jnp.stack(vals5)), ref5)
        assert np.array_equal(np.asarray(jnp.stack(vals7)), ref7)
        # the crashed worker is tripped offline; work migrated to w2
        assert rt.dispatcher.fleet.state("w1") == "offline"
        summary = rt.telemetry.summary()
        assert summary["migrated_batches"] >= 1
        assert summary["fleet"]["w1"]["failures"] >= 1
        assert summary["fleet"]["w1"]["migrations"] >= 1
        assert summary["fleet"]["w1"]["offline_trips"] >= 1
    finally:
        rt.close()


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_terminal_failure_fails_futures(specs, mode):
    """Both workers crashed: no survivor to migrate to — the batch's
    futures must resolve with the error (not hang).  The sync driver
    raises it from drain(); the async driver puts it in its error ring."""
    cfg5, _ = specs
    ft = FaultToleranceConfig(retry_limit=0, breaker_threshold=1)
    inj = FaultInjector(
        {
            "w1": FaultSpec(kind="crash", at=0.0),
            "w2": FaultSpec(kind="crash", at=0.0),
        }
    )
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 10), WorkerConfig("w2", 10)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode=mode,
        fault_tolerance=ft,
        fault_injector=inj,
    )
    try:
        theta, data = rows_for(cfg5, 8)
        now = rt.dispatcher.clock
        futs = [
            rt.gateway.submit("alice", cfg5.spec, (theta[i], data[i]), now())
            for i in range(8)
        ]
        if mode == "sync":
            with pytest.raises(InjectedWorkerFault):
                rt.dispatcher.drain()
            assert all(f.done for f in futs)
        else:
            rt.dispatcher.drain()
            assert any(
                isinstance(e, InjectedWorkerFault) for e in rt.dispatcher.errors
            )
        for f in futs:
            with pytest.raises(InjectedWorkerFault):
                f.result(timeout=1.0)
    finally:
        rt.close()


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_transient_failure_retries_in_place(specs, mode):
    """A kernel that fails exactly once recovers via the in-place retry —
    no migration, and the retry is visible in fleet telemetry."""
    cfg5, _ = specs
    boom = {"n": 0}

    def flaky_kernel(spec, theta, data):
        boom["n"] += 1
        if boom["n"] == 1:
            raise RuntimeError("transient kernel fault")
        return kops.vqc_fidelity(spec, theta, data)

    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 10)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode=mode,
        kernel=flaky_kernel,
        fault_tolerance=FaultToleranceConfig(retry_limit=2, breaker_threshold=5),
    )
    try:
        theta, data = rows_for(cfg5, 8)
        now = rt.dispatcher.clock
        futs = [
            rt.gateway.submit("alice", cfg5.spec, (theta[i], data[i]), now())
            for i in range(8)
        ]
        if mode == "sync":
            rt.dispatcher.drain()
        else:
            rt.dispatcher.kick()
        vals = [f.result(timeout=60.0) for f in futs]
        ref = np.asarray(kops.vqc_fidelity(cfg5.spec, theta, data))
        assert np.array_equal(np.asarray(jnp.stack(vals)), ref)
        summary = rt.telemetry.summary()
        assert summary["fleet"]["w1"]["retries"] == 1
        assert "migrated_batches" not in summary
        assert rt.dispatcher.fleet.state("w1") in ("idle", "busy")
    finally:
        rt.close()


# ----------------------------------------------------------------- hedging
def test_async_hedge_first_result_wins(specs):
    """A stalled primary slot past hedge_k x the EWMA estimate gets a
    duplicate dispatch on another worker; the duplicate's result resolves
    the futures while the straggler is still stuck, and the straggler's
    late result is discarded without double-resolution."""
    cfg5, _ = specs
    gate = threading.Event()
    calls = {"n": 0}

    def stall_first_kernel(spec, theta, data):
        calls["n"] += 1
        if calls["n"] == 1:
            assert gate.wait(timeout=30.0), "test gate never released"
        return kops.vqc_fidelity(spec, theta, data)

    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 10), WorkerConfig("w2", 10)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode="async",
        kernel=stall_first_kernel,
        fault_tolerance=FaultToleranceConfig(hedge_k=0.05, breaker_threshold=10),
    )
    try:
        theta, data = rows_for(cfg5, 8)
        now = rt.dispatcher.clock
        futs = [
            rt.gateway.submit("alice", cfg5.spec, (theta[i], data[i]), now())
            for i in range(8)
        ]
        rt.dispatcher.kick()
        vals = [f.result(timeout=60.0) for f in futs]  # hedge resolved these
        assert not gate.is_set()
        ref = np.asarray(kops.vqc_fidelity(cfg5.spec, theta, data))
        assert np.array_equal(np.asarray(jnp.stack(vals)), ref)
        summary = rt.telemetry.summary()
        hedges = sum(ev["hedges"] for ev in summary["fleet"].values())
        assert hedges >= 1
    finally:
        gate.set()
        rt.close()
        # the straggler settled without touching the already-set futures
        assert all(f.done for f in futs)


# --------------------------------------------------------- live membership
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_register_worker_adds_capacity_at_runtime(specs, mode):
    """A fleet of one 5q worker cannot host 7q circuits; registering a 10q
    worker at runtime makes them servable without a restart."""
    cfg5, cfg7 = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode=mode,
    )
    try:
        rt.dispatcher.register_worker(WorkerConfig("w2", 10))
        assert set(rt.dispatcher.fleet.workers()) == {"w1", "w2"}
        t7, d7 = rows_for(cfg7, 8)
        now = rt.dispatcher.clock
        futs = [
            rt.gateway.submit("alice", cfg7.spec, (t7[i], d7[i]), now())
            for i in range(8)
        ]
        if mode == "sync":
            rt.dispatcher.drain()
        else:
            rt.dispatcher.kick()
        vals = [f.result(timeout=60.0) for f in futs]
        ref = np.asarray(kops.vqc_fidelity(cfg7.spec, t7, d7))
        assert np.array_equal(np.asarray(jnp.stack(vals)), ref)
        with pytest.raises(ValueError):
            rt.dispatcher.register_worker(WorkerConfig("w2", 10))  # duplicate
    finally:
        rt.close()


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_drain_worker_removes_it_gracefully(specs, mode):
    """Draining waits for in-flight work, then forgets the worker: it stops
    being placeable and later submissions run entirely on the survivors."""
    cfg5, _ = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 10), WorkerConfig("w2", 10)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode=mode,
    )
    try:
        theta, data = rows_for(cfg5, 8)
        now = rt.dispatcher.clock
        futs = [
            rt.gateway.submit("alice", cfg5.spec, (theta[i], data[i]), now())
            for i in range(8)
        ]
        if mode == "sync":
            rt.dispatcher.drain()
        else:
            rt.dispatcher.kick()
        for f in futs:
            f.result(timeout=60.0)
        rt.dispatcher.drain_worker("w1")
        assert "w1" not in rt.dispatcher.fleet.workers()
        assert "w1" not in rt.dispatcher.manager.workers
        futs2 = [
            rt.gateway.submit("alice", cfg5.spec, (theta[i], data[i]), now())
            for i in range(8)
        ]
        if mode == "sync":
            rt.dispatcher.drain()
        else:
            rt.dispatcher.kick()
        for f in futs2:
            f.result(timeout=60.0)
        with pytest.raises(KeyError):
            rt.dispatcher.drain_worker("nope")
    finally:
        rt.close()


# ----------------------------------------------- bounded error ring buffer
def test_async_error_ring_is_bounded(specs):
    cfg5, _ = specs
    rt = GatewayRuntime(
        workers=[WorkerConfig("w1", 5)],
        target=8,
        lanes=8,
        deadline=0.05,
        mode="async",
    )
    try:
        d = rt.dispatcher
        cap = d.ERRORS_CAPACITY
        with d._cv:
            for i in range(cap + 10):
                d._push_error_locked(RuntimeError(f"e{i}"))
        assert len(d.errors) == cap
        assert d.errors_dropped == 10
        # oldest entries were evicted, newest retained
        assert str(d.errors[-1]) == f"e{cap + 9}"
    finally:
        rt.close()


# ------------------------------------------------- simulation fault parity
def test_sim_crash_recover_completes_all_jobs():
    sim = SystemSimulation(
        homogeneous_workers(3, 10),
        two_jobs(),
        heartbeat_period=1.0,
        worker_failures={
            "w1": FaultSpec(kind="crash_recover", at=0.2, recover_at=5.0)
        },
    )
    r = sim.run()
    assert r.total_circuits == 60
    assert set(r.jobs) == {"alice", "bob"}
    # the recovered worker re-registered and did real work afterwards
    assert "w1" in sim.manager.workers


def test_sim_slowdown_stretches_makespan():
    base = SystemSimulation(homogeneous_workers(2, 10), two_jobs()).run()
    slow = SystemSimulation(
        homogeneous_workers(2, 10),
        two_jobs(),
        worker_failures={"w1": {"kind": "slowdown", "at": 0.0, "factor": 4.0}},
    ).run()
    assert slow.total_circuits == base.total_circuits == 60
    assert slow.makespan > base.makespan


def test_sim_flaky_worker_completes_via_requeue():
    r = SystemSimulation(
        homogeneous_workers(2, 10),
        two_jobs(),
        worker_failures={"w1": {"kind": "flaky", "p": 0.4}},
    ).run()
    assert r.total_circuits == 60 and set(r.jobs) == {"alice", "bob"}


def test_sim_gateway_crash_recover_migrates_batches():
    r = SystemSimulation(
        homogeneous_workers(3, 10),
        two_jobs(),
        gateway=True,
        gateway_deadline=0.2,
        heartbeat_period=1.0,
        worker_failures={
            "w1": FaultSpec(kind="crash_recover", at=0.1, recover_at=6.0)
        },
    ).run()
    assert set(r.jobs) == {"alice", "bob"}
    assert r.gateway_summary["migrated_batches"] >= 1
    assert r.gateway_summary["migrated_circuits"] >= 1


def test_sim_gateway_flaky_requeues_through_coalescer():
    r = SystemSimulation(
        homogeneous_workers(2, 10),
        two_jobs(),
        gateway=True,
        gateway_deadline=0.2,
        worker_failures={"w1": {"kind": "flaky", "p": 0.5}},
    ).run()
    assert set(r.jobs) == {"alice", "bob"}
    assert r.gateway_summary["migrated_batches"] >= 1
