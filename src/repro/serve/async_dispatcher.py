"""Async serving runtime: non-blocking driver of the dispatch core.

The synchronous driver (``Dispatcher.run_batch``) executes every coalesced
mega-batch inline on the submitting thread, so one slow kernel launch
head-of-line-blocks every tenant — exactly the uncontrolled behavior the
paper's co-Manager exists to avoid.  ``AsyncDispatcher`` drives the same
core steps (``_place``, ``_execute``, ``_execute_spilled``) from decoupled
stages:

  * a PUMP THREAD moves admitted circuits through the weighted-fair
    scheduler and the coalescer, places emitted batches via Algorithm 2,
    and re-arms itself on the coalescer's next SLO/deadline flush;
  * a WORKER POOL executes placed batches — each registered worker owns
    ``slots_per_worker`` execution slots, one in-flight mega-batch each, so
    distinct workers (and slots) overlap kernel execution with admission,
    coalescing, and placement;
  * ``CircuitFuture``s resolve OUT OF ORDER as their batches finish; a
    batch that cannot currently be placed waits in a ready queue without
    blocking later batches that fit another worker.

Placement charges each batch's EWMA-predicted service seconds to the chosen
worker's CRU for the time it is outstanding (see ``repro.serve.dispatcher``),
so Algorithm 2 keeps steering work toward the least-loaded worker even
though completions now arrive asynchronously.

Locking: the gateway has its own re-entrant lock; the core's condition
variable ``_cv`` guards the scheduler state (ready queue, slot counts,
co-Manager views).  The two are never held nested in the
gateway-then-condition order, so there is no lock-ordering cycle.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Sequence

from repro.comanager.worker import WorkerConfig
from repro.serve.coalescer import CoalescedBatch
from repro.serve.dispatcher import Dispatcher
from repro.serve.gateway import Gateway


class AsyncDispatcher(Dispatcher):
    """Non-blocking dispatcher: pump loop + per-worker execution pool."""

    #: ring-buffer capacity for execution errors kept for inspection — a
    #: long-lived dispatcher on a flaky fleet must not grow an unbounded
    #: error list; overflow increments ``errors_dropped`` instead.
    ERRORS_CAPACITY = 256

    def __init__(
        self,
        gateway: Gateway,
        workers: Sequence[WorkerConfig],
        *,
        slots_per_worker: int = 1,
        evict_over_slo: bool = False,
        **kw,
    ):
        """``kw``: the ``Dispatcher`` options."""
        super().__init__(gateway, workers, **kw)
        if slots_per_worker < 1:
            raise ValueError(f"slots_per_worker must be >= 1, got {slots_per_worker}")
        self.slots_per_worker = slots_per_worker
        #: preemptively evict ready-queue batches whose every member's SLO
        #: budget has fully elapsed (guaranteed misses): their futures
        #: resolve with DeadlineExceeded and the capacity serves work that
        #: can still make its deadline.  Off by default — eviction turns
        #: late results into errors, which only SLO-strict serving wants.
        self.evict_over_slo = evict_over_slo
        self._slot_free = {w.worker_id: slots_per_worker for w in workers}
        self._spill_slot_free = True  # one whole-mesh batch at a time
        self._ready: list[CoalescedBatch] = []
        self._in_flight = 0
        self._pumping = False  # a _pump_once holds popped-but-unqueued batches
        self._kicked = False
        self._stop = False
        self._errors: deque[BaseException] = deque(maxlen=self.ERRORS_CAPACITY)
        self._errors_dropped = 0
        self._pump_errors: list[BaseException] = []
        # +1 thread: the whole-mesh spill slot runs alongside full worker pools
        self._pool = ThreadPoolExecutor(
            max_workers=len(workers) * slots_per_worker + 1,
            thread_name_prefix="serve-slot",
        )
        self._pump_thread: threading.Thread | None = None

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        """Launch the pump thread (idempotent)."""
        if self._pump_thread is not None and self._pump_thread.is_alive():
            return
        self._stop = False
        self._pump_thread = threading.Thread(
            target=self._pump_loop, name="serve-pump", daemon=True
        )
        self._pump_thread.start()

    def close(self) -> None:
        """Stop the pump thread and wait for in-flight batches to finish."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=10.0)
            self._pump_thread = None
        self._pool.shutdown(wait=True)

    def __enter__(self) -> "AsyncDispatcher":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def kick(self) -> None:
        """Wake the pump loop (call after submitting work)."""
        with self._cv:
            self._kicked = True
            self._cv.notify_all()

    # ----------------------------------------------------------- pump loop
    def _wait_timeout(self) -> float | None:
        """Seconds until the pump must wake for a deadline flush; a short
        safety poll while batches wait for capacity; None to sleep until
        kicked/notified."""
        nd = self.gateway.next_deadline()
        timeout = None
        with self._cv:
            if self._ready:
                timeout = 0.05
            if self.ft.hedge_k is not None and self._runners:
                # hedging watches in-flight slots against the EWMA estimate
                timeout = 0.01 if timeout is None else min(timeout, 0.01)
        if nd is not None:
            until = max(nd - self.clock(), 1e-3)
            timeout = until if timeout is None else min(timeout, until)
        return timeout

    def _pump_loop(self) -> None:
        while True:
            timeout = self._wait_timeout()
            with self._cv:
                if self._stop:
                    return
                if not self._kicked:
                    self._cv.wait(timeout)
                self._kicked = False
                if self._stop:
                    return
            try:
                self._pump_once()
            except Exception as exc:  # keep the loop alive; drain() raises it
                with self._cv:
                    self._pump_errors.append(exc)
                    self._cv.notify_all()

    def _pump_once(self) -> None:
        # _pumping marks the window where batches have been popped from the
        # gateway but not yet queued in _ready: drain() must not conclude
        # "quiesced" while their futures are still in limbo.
        with self._cv:
            self._pumping = True
        try:
            batches = self.gateway.pump(self.clock())
            with self._cv:
                self._ready.extend(batches)
        finally:
            with self._cv:
                self._pumping = False
                self._cv.notify_all()
        self._place_ready()
        self._maybe_hedge()

    def _expired(self, batch: CoalescedBatch, now: float) -> bool:
        """True when EVERY member's SLO budget has fully elapsed: the batch
        is a guaranteed miss for all of them, so executing it can only
        delay work that might still make its deadline.  A member without an
        SLO (best-effort) keeps the batch alive — its result is still
        wanted whenever it arrives."""
        saw_slo = False
        for m in batch.members:
            st = self.gateway.tenants.get(m.client_id)
            if st is None or st.slo_s is None:
                return False
            saw_slo = True
            if now <= m.arrival + st.slo_s:
                return False
        return saw_slo

    def _place_ready(self) -> None:
        """Try to place every ready batch; no head-of-line blocking — a
        batch that fits no worker right now is skipped, later batches may
        fit a different worker.  Oversized batches (register width or VMEM
        model above every worker) route to the whole-mesh spill slot;
        fully-over-SLO batches are preemptively evicted when enabled."""
        while True:
            now = self.clock()
            job = evict = None
            with self._cv:
                exclude = {
                    w for w, free in self._slot_free.items() if free <= 0
                } | self.fleet.unplaceable(now)
                for i, batch in enumerate(self._ready):
                    if self.evict_over_slo and self._expired(batch, now):
                        evict = self._ready.pop(i)
                        break
                    if self.mesh_spill and self._oversized(batch):
                        if not self._spill_slot_free:
                            continue  # mesh busy; later batches may fit workers
                        self._spill_slot_free = False
                        self._in_flight += 1
                        self._ready.pop(i)
                        self._stage(batch, now, "placed", worker="mesh")
                        job = (self._execute_spilled, batch)
                        break
                    width = self._width(batch)
                    if not self.mesh_spill and width > self._max_width:
                        # spill disabled: the pre-spill contract — fail fast
                        # on register width only (a VMEM-model-heavy batch
                        # that fits a worker's register still executes there)
                        self._ready.pop(i)
                        err = RuntimeError(
                            f"no worker fits a {width}-qubit batch "
                            f"(largest worker: {self._max_width} qubits)"
                        )
                        self._push_error_locked(err)
                        self.gateway.fail(batch, err, now)
                        break
                    placed = self._place(batch, now, exclude)
                    if placed is None:
                        continue
                    _, wid, est = placed
                    self._ready.pop(i)
                    self._slot_free[wid] -= 1
                    self._in_flight += 1
                    self._runners[id(batch)] = {
                        "batch": batch,
                        "outstanding": 1,
                        "winner": None,
                        "wid": wid,
                        "t0": now,
                        "est": est,
                        "hedged": False,
                    }
                    job = (self._execute, batch, *placed)
                    break
                else:
                    return  # nothing placeable right now
            if evict is not None:
                self.gateway.evict(evict, now)
            elif job is not None:
                self._pool.submit(*job)

    def _release(self, wid: str | None, err: BaseException | None) -> None:
        """Free the settled batch's slot (the spill slot when ``wid`` is
        None), ring its error, and wake the pump: ready batches may now
        place."""
        if wid is None:
            self._spill_slot_free = True
        elif wid in self._slot_free:  # the worker may have been drained
            self._slot_free[wid] += 1
        self._in_flight -= 1
        if err is not None:
            self._push_error_locked(err)
        self._kicked = True
        self._cv.notify_all()

    def _maybe_hedge(self) -> None:
        """Hedged duplicate dispatch: an in-flight batch whose slot has
        exceeded ``hedge_k x`` its ServiceModel estimate is duplicated onto
        a free surviving worker; first result wins."""
        k = self.ft.hedge_k
        if k is None:
            return
        now = self.clock()
        launches = []
        with self._cv:
            for entry in self._runners.values():
                if entry["hedged"] or entry["winner"] is not None:
                    continue
                if now - entry["t0"] < k * max(entry["est"], 1e-9):
                    continue
                batch = entry["batch"]
                width = self._width(batch)
                wid2 = None
                for w in sorted(self._slot_free):
                    if w == entry["wid"] or self._slot_free[w] <= 0:
                        continue
                    v = self.manager.workers.get(w)
                    if v is None or v.max_qubits < width:
                        continue
                    if not self.fleet.placeable(w, now):
                        continue
                    wid2 = w
                    break
                if wid2 is None:
                    continue
                entry["hedged"] = True
                entry["outstanding"] += 1
                self._slot_free[wid2] -= 1
                self._in_flight += 1
                self._charge(wid2, entry["est"])
                self.fleet.on_dispatch(wid2)
                launches.append((batch, entry["wid"], wid2, entry["est"]))
        for batch, straggler, wid2, est in launches:
            self.fleet.record_hedge(straggler)
            self.gateway.telemetry.on_worker_hedge(straggler)
            self._stage(batch, now, "hedged", worker=wid2)
            self._pool.submit(self._execute, batch, None, wid2, est, True)

    # ------------------------------------------------------------- control
    def pump(self) -> int:
        """Non-blocking: wake the pump loop and return immediately."""
        self.kick()
        return 0

    def drain(self) -> int:
        """Force-flush partial buffers and block until the gateway is idle
        and every in-flight batch has resolved its futures.  Returns the
        number of batches executed while draining.  Raises the first pump-
        loop error instead of spinning forever on a wedged pump."""
        self.start()
        n0 = len(self.batch_log)
        while True:
            batches = self.gateway.flush(self.clock())
            with self._cv:
                if self._pump_errors:
                    raise self._pump_errors[0]
                self._ready.extend(batches)
                self._kicked = True
                self._cv.notify_all()
                quiesced = (
                    not self._ready
                    and self._in_flight == 0
                    and not self._pumping
                )
            if quiesced and self.gateway.idle:
                break
            with self._cv:
                self._cv.wait(0.02)
        return len(self.batch_log) - n0

    def absorb_backpressure(self) -> None:
        """Backpressure-retry hook: wake the pump, then wait briefly for a
        completion to free queue space — WITHOUT quiescing the whole runtime
        (the sync dispatcher has no choice but to drain inline; here a full
        drain would collapse the submission/execution overlap)."""
        self.kick()
        with self._cv:
            if self._pump_errors:
                raise self._pump_errors[0]
            self._cv.wait(0.05)

    # ------------------------------------------------------ live membership
    def register_worker(self, worker: WorkerConfig) -> None:
        """Grow the fleet at runtime: the new worker gets its execution
        slots and becomes placeable on the next pump cycle."""
        # manager.workers is read under _cv by the pump and runner threads,
        # so membership mutations happen under the same lock
        with self._cv:
            super().register_worker(worker)
            self._slot_free[worker.worker_id] = self.slots_per_worker
            # grow the slot pool so the new worker's slots can actually run
            # concurrently (ThreadPoolExecutor spawns threads on demand up
            # to _max_workers, so raising the cap is safe at runtime)
            self._pool._max_workers += self.slots_per_worker
            self._kicked = True
            self._cv.notify_all()

    def drain_worker(self, worker_id: str, timeout: float = 30.0) -> None:
        """Live drain: stop placing on the worker, wait for its in-flight
        slots to land (results resolve, or migrate through the failure
        path), then remove it from the fleet."""
        deadline = time.monotonic() + timeout
        with self._cv:
            if worker_id not in self._slot_free:
                raise KeyError(f"unknown worker {worker_id!r}")
            self.fleet.mark_draining(worker_id)
            while self._slot_free[worker_id] < self.slots_per_worker:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"drain_worker({worker_id!r}): in-flight work did "
                        f"not land within {timeout}s"
                    )
                self._cv.wait(min(remaining, 0.05))
            del self._slot_free[worker_id]
            self._forget_worker(worker_id)
        self.kick()

    # ------------------------------------------------------------- metrics
    @property
    def in_flight_batches(self) -> int:
        with self._cv:
            return self._in_flight

    def _push_error_locked(self, err: BaseException) -> None:
        """Append to the bounded error ring (caller holds ``_cv``)."""
        if len(self._errors) == self._errors.maxlen:
            self._errors_dropped += 1
        self._errors.append(err)

    @property
    def errors(self) -> list[BaseException]:
        with self._cv:
            return list(self._pump_errors) + list(self._errors)

    @property
    def errors_dropped(self) -> int:
        """Errors evicted from the bounded ring (oldest-first overflow)."""
        with self._cv:
            return self._errors_dropped
