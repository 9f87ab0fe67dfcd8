"""Open-loop inference through ``QuantumCluster``: requests arrive on a
schedule fixed by the traffic file's rate, whether or not earlier ones are
done, and each classifies one 8x8 digit: every (class, patch) pair is one
row circuit sent with ``Session.submit``.

The schedule has a fixed number of requests, rate x seconds, and a fixed
set of inter-arrival gaps (the quantiles of the exponential distribution,
so Poisson-like); the seed only orders the gaps, assigns tenants in equal
shares and draws the images.  A request is timed from its scheduled send
to the moment all of its fidelities are on the host; a refused or failed
request counts as infinitely late.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np

from bench import digits, reference, workcount
from bench.cluster import cluster, qcfg, telemetry, telemetry_delta
from bench.harness import sub_seed

WINDOW_SPAN = "bench.window"


def schedule(tr: dict, seconds: float, rng: np.random.Generator):
    """(send offsets (N,), tenant of each (N,)) for one window."""
    n = max(1, round(tr["rate_per_s"] * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / tr["rate_per_s"]
    rng.shuffle(gaps)
    tenants = np.arange(n) % tr["tenants"]
    rng.shuffle(tenants)
    return np.cumsum(gaps) - gaps[0], tenants


def requests(cfg: dict, tr: dict, params: dict, n: int, rng: np.random.Generator):
    """n requests as (theta (n, R, P), data (n, R, D)) f32 rows, R = classes
    x patches, class-major."""
    images = digits.digit_set(tr["digits"], n, rng)
    angles = np.asarray(reference.encode(params, reference.clean(images)))
    per = angles.shape[0] // n
    angles = angles.reshape(n, per, -1)
    theta = np.asarray(params["theta"], np.float32)
    c = theta.shape[0]
    t = np.broadcast_to(theta[None, :, None, :], (n, c, per, theta.shape[1]))
    d = np.broadcast_to(angles[:, None], (n, c, per, angles.shape[-1]))
    return (
        np.ascontiguousarray(t.reshape(n, c * per, -1), np.float32),
        np.ascontiguousarray(d.reshape(n, c * per, -1), np.float32),
    )


def inputs(cfg: dict, tr: dict, seed: int, seconds: float, part: int = 3):
    """(offsets, tenants, theta, data) of one schedule; ``part`` 3 is the
    window, 1 the warm-up."""
    rng = np.random.default_rng(sub_seed(seed, part))
    offsets, tenants = schedule(tr, seconds, rng)
    return (offsets, tenants, *requests(cfg, tr, reference.params_of(cfg, sub_seed(seed, 2)), len(offsets), rng))


def setup(cfg: dict, tr: dict, seed: int, seconds: float) -> dict:
    from repro.api import TenantPolicy

    cl = cluster(cfg, tr)
    policy = TenantPolicy(**tr["policy"])
    state = {
        "cfg": cfg,
        "traffic": tr,
        "spec": qcfg(cfg).spec,
        "cluster": cl,
        "sessions": [cl.session(f"tenant-{i}", policy) for i in range(tr["tenants"])],
    }
    state["window"] = inputs(cfg, tr, seed, seconds)
    run_schedule(state, *inputs(cfg, tr, seed, tr["warmup_s"], part=1), span="bench.warmup")
    return state


def run_schedule(state, offsets, tenants, theta, data, span):
    """Send every request at its offset; returns per-request
    (scheduled, done, fidelities or None) and the loop's start."""
    import jax
    from repro.serve.gateway import Backpressure

    spec, sessions = state["spec"], state["sessions"]
    n, rows = theta.shape[0], theta.shape[1]
    futs: list = [None] * n
    sched = np.zeros(n)
    done = np.full(n, math.inf)
    fids: list = [None] * n
    sent, stop = threading.Event(), threading.Event()
    next_sent = [0]

    def collect():
        finished = np.zeros(n, bool)
        first = 0
        while not stop.is_set():
            top = next_sent[0]
            for j in range(first, top):
                fs = futs[j]
                if finished[j] or (fs is not None and not all(f.done for f in fs)):
                    continue
                if fs is not None:
                    try:
                        fids[j] = np.asarray(jax.device_get([f.value for f in fs]))
                        done[j] = time.perf_counter()
                    except Exception:  # noqa: BLE001 - a failed request stays late
                        pass
                finished[j] = True
            while first < top and finished[first]:
                first += 1
            if sent.is_set() and first >= n:
                return
            time.sleep(0.0002)

    collector = threading.Thread(target=collect, daemon=True)
    with jax.profiler.TraceAnnotation(span):
        t0 = time.perf_counter() + 0.01
        collector.start()
        for i in range(n):
            sched[i] = t0 + offsets[i]
            wait = sched[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sess = sessions[tenants[i]]
            try:
                futs[i] = [sess.submit(spec, theta[i, r], data[i, r]) for r in range(rows)]
            except Backpressure:
                futs[i] = None
            next_sent[0] = i + 1
        sent.set()
        collector.join(timeout=state["traffic"]["wait_after_s"] + offsets[-1])
        stop.set()
        collector.join()
    return sched, done, fids, t0


def window(state: dict, seconds: float) -> dict:
    cfg = state["cfg"]
    before = telemetry(state["cluster"])
    offsets, tenants, theta, data = state["window"]
    sched, done, fids, t0 = run_schedule(state, offsets, tenants, theta, data, WINDOW_SPAN)
    after = telemetry(state["cluster"])
    ok = np.isfinite(done)
    rows = theta.shape[1]
    state["fids"] = fids
    t_end = max(float(done[ok].max()) if ok.any() else t0, t0 + seconds)
    return {
        "window_s": t_end - t0,
        "circuits": int(ok.sum()) * rows,
        "requests": len(done),
        "failed": int((~ok).sum()),
        "latencies_s": list(done - sched),
        "kernel_work": {"rows": (1, workcount.rows_work(cfg, int(ok.sum()) * rows))},
        "telemetry": telemetry_delta(before, after),
    }


def close(state: dict) -> None:
    state["cluster"].close()
    state.pop("cluster")
    state.pop("sessions")


def readings(cfg: dict, window_inputs, served: list, dtype=None) -> dict:
    """Widest gap between a served fidelity and the reference's, over every
    request of the window that was served.  ``dtype`` puts the reference,
    computed in that precision, in the program's place (the control)."""
    ops = reference.circuit_of(cfg)
    _, _, theta, data = window_inputs
    idx = [i for i, f in enumerate(served) if f is not None]
    if not idx:
        return {"fidelity_gap": math.inf}
    t = theta[idx].reshape(-1, theta.shape[-1])
    d = data[idx].reshape(-1, data.shape[-1])
    want = np.asarray(reference.fidelities(ops, t, d), np.float64)
    if dtype is None:
        got = np.concatenate([np.asarray(served[i], np.float64).ravel() for i in idx])
    else:
        got = np.asarray(reference.fidelities(ops, t, d, dtype=dtype), np.float64)
    return {"fidelity_gap": float(np.max(np.abs(got - want)))}


def check(state: dict, result: dict, limits: dict) -> tuple[int, int, list]:
    nums = readings(state["cfg"], state["window"], state["fids"])
    checks = [(k, v, limits[k]) for k, v in nums.items()]
    return result["requests"], result["failed"], checks
