"""Bring-up smoke test: the QuClassi train-and-serve path on one TPU chip.

Drives the main path once, through the entry points a user calls, at the
paper's widest configuration (``quclassi-7q-3l``) with compiled Mosaic
kernels, and checks every result against the plain ``core.sim`` simulator
(run at ``Precision.HIGHEST``):

  train  ``QuantumCluster`` (async serving) -> ``Session.train`` with
         implicit shift banks: gateway -> co-Manager -> dispatcher ->
         prefix-reuse kernel.  The first step's gradient is recomputed
         through the same session and compared with the reference.
  serve  four tenants at once: two submit row circuits, two run implicit
         shift banks; every fidelity is compared with the reference.
  spill  one 17-qubit / 3-layer shift bank (register width m = 8) through
         the depth-tiled spill kernel, compared on a few lanes.

Every phase fails on a failed future, a dispatcher error, or a retry,
migration, eviction or worker failure in the telemetry: the dispatchers'
fault tolerance must not be able to hide a kernel that did not run.

``--chips 4`` runs only the multi-chip paths instead, on a 4-device
``data`` mesh: the ``sharded`` and ``mesh_spill`` backends and the
dispatcher's mesh-spill path, each on one shift bank and one multi-bank
set, compared with the one-device kernel.

Usage:  python chip_smoke.py [--chips 4]

The last line of standard output is one JSON object, printed only when
every phase passed on a TPU:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
Without a TPU, or outside a checkout of this repository, it exits nonzero.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

SRC = Path(__file__).resolve().parent / "src"
sys.path.insert(0, str(SRC))

SEED = 0
CONFIG = "quclassi-7q-3l"
#: images per training step: 16 images x 9 patches = 144 lanes per class
#: bank, so every launch fills at least one 128-lane tile.
TRAIN_BATCH = 16
TRAIN_STEPS = 2
SERVE_ROWS = 192  # row circuits per row tenant
SERVE_SAMPLES = 128  # samples per implicit bank
SPILL_QUBITS, SPILL_LAYERS, SPILL_SAMPLES = 17, 3, 512
#: kernel vs reference on a fidelity: both are f32 statevector simulations
#: of the same circuit that differ in operation order and in the TPU's
#: cos/sin, which leaves a few ulps per gate.
FID_TOL = 1e-5
#: multi-chip paths vs the one-device kernel: same kernel, same lanes.
SHARD_TOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX found {len(devices)}")
    return devices


def timed(fn, *args, **kw):
    """(result, seconds), the clock stopped after the device finished."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def max_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))))


def check(name: str, err: float, tol: float) -> None:
    if not err <= tol:  # also catches NaN
        raise AssertionError(f"{name}: max abs error {err:.3e} > {tol:.1e}")


def reference_fidelities(spec, theta, data):
    from repro.core import fidelity as fid

    return jax.jit(fid.fidelity_batch, static_argnums=0)(spec, theta, data)


def uniform(rng, shape):
    return jnp.asarray(rng.uniform(0.0, np.pi, shape), jnp.float32)


def assert_no_faults(cluster) -> dict:
    """Fail on anything the dispatchers' fault tolerance absorbed."""
    errors = cluster.runtime.dispatcher.errors
    if errors:
        raise AssertionError(f"dispatcher errors: {errors!r}")
    summary = cluster.telemetry.summary()
    events = {
        w: {k: v for k, v in ev.items() if v}
        for w, ev in summary.get("fleet", {}).items()
    }
    events = {w: ev for w, ev in events.items() if ev}
    if events:
        raise AssertionError(f"worker fault events: {events}")
    for key in ("migrated_batches", "evicted"):
        if summary.get(key):
            raise AssertionError(f"telemetry {key} = {summary[key]}")
    for row in summary["tenants"]:
        if row["rejected"] or row["completed"] != row["submitted"]:
            raise AssertionError(f"tenant not fully served: {row}")
    return summary


def mode_of(spec, lanes: int) -> str:
    from repro.kernels.vqc_statevector import shift_execution_info

    info = shift_execution_info(spec, lanes)
    return f"{info['mode']} ({info['launches']} launch{'es' * (info['launches'] > 1)})"


# --------------------------------------------------------------- phases
def train_phase(cluster) -> None:
    from repro.configs.quclassi_paper import get_quclassi
    from repro.core import fidelity as fid
    from repro.core import quclassi, shift_rule
    from repro.data import mnist, pipeline

    qcfg = get_quclassi(CONFIG)
    spec = qcfg.spec
    x, y = mnist.make_pair_dataset(3, 9, n_per_class=21, seed=SEED)
    (xtr, ytr), test_set = mnist.train_test_split(x, y)
    n_train = TRAIN_STEPS * TRAIN_BATCH
    xtr, ytr = xtr[:n_train], ytr[:n_train]
    sess = cluster.session("trainer", bank_mode="implicit")

    # the first step's gradient: the trainer's initial parameters and first
    # batch, through the session's gateway-backed executor
    params0 = quclassi.init_params(qcfg, jax.random.PRNGKey(SEED))
    xb, yb = next(
        pipeline.batches(pipeline.clean(xtr), ytr, TRAIN_BATCH, seed=SEED * 997)
    )
    xb, yb = jnp.asarray(xb), jnp.asarray(yb)
    (loss, grads, f), t_grad = timed(
        quclassi.grad_shift,
        qcfg,
        params0,
        xb,
        yb,
        executor=sess.executor(spec),
        implicit=True,
    )
    loss_r, grads_r, f_r = quclassi.grad_shift(
        qcfg,
        params0,
        xb,
        yb,
        executor=shift_rule.default_executor(spec),
        implicit=False,
    )
    # dL/dF = (F - y) / (F (1 - F)): a fidelity error eps moves the loss by
    # at most eps * A and each theta gradient by eps * mean(A + A^2 / 2) / C
    # (A = 1 / (F (1 - F)), |dF/dtheta| <= 1/2), to first order.
    fr = np.clip(np.asarray(f_r, np.float64), fid._EPS, 1 - fid._EPS)
    amp = 1.0 / (fr * (1.0 - fr))
    loss_tol = FID_TOL * float(amp.mean())
    grad_tol = FID_TOL * float((amp + amp**2 / 2).mean(0).max()) / qcfg.n_classes
    f_err = max_err(f, f_r)
    g_err = max_err(grads["theta"], grads_r["theta"])
    l_err = max_err(loss, loss_r)
    check("train fidelities", f_err, FID_TOL)
    check("train theta gradient", g_err, grad_tol)
    check("train loss", l_err, loss_tol)

    report, t_train = timed(
        sess.train,
        qcfg,
        (xtr, ytr),
        test_set,
        epochs=1,
        batch_size=TRAIN_BATCH,
        lr=0.05,
        seed=SEED,
    )
    rec = report.epochs[0]
    if not math.isfinite(rec.loss):
        raise AssertionError(f"training loss is not finite: {rec.loss}")
    for name, p in report.params.items():
        if not bool(jnp.all(jnp.isfinite(p))):
            raise AssertionError(f"parameter {name} is not finite after training")
    summary = assert_no_faults(cluster)
    lanes = TRAIN_BATCH * qcfg.n_patches
    log(
        f"train: {CONFIG} steps={TRAIN_STEPS} lanes/bank={lanes} "
        f"kernel={mode_of(spec, lanes)} loss={rec.loss:.6f} "
        f"circuits={rec.circuits_executed} wall_s={t_train:.3f}"
    )
    log(
        f"train: step-1 check vs core.sim: fid max_abs_err={f_err:.3e} "
        f"(tol {FID_TOL:.0e}) theta-grad max_abs_err={g_err:.3e} "
        f"(tol {grad_tol:.3e}) loss abs_err={l_err:.3e} (tol {loss_tol:.3e}) "
        f"grad_wall_s={t_grad:.3f}"
    )
    log(
        f"train: telemetry completed={summary['total_completed']} "
        f"batches={summary['batches']} fused_launches={summary.get('fused_launches', 0)}"
    )


def serve_phase(cluster) -> None:
    from repro.configs.quclassi_paper import get_quclassi
    from repro.core import shift_rule

    spec7 = get_quclassi(CONFIG).spec
    spec5 = get_quclassi("quclassi-5q-2l").spec
    rng = np.random.default_rng(SEED + 1)

    def rows(spec):
        return uniform(rng, (SERVE_ROWS, spec.n_theta)), uniform(
            rng, (SERVE_ROWS, spec.n_data)
        )

    def bank(spec):
        return shift_rule.build_shift_bank(
            uniform(rng, (spec.n_theta,)), uniform(rng, (SERVE_SAMPLES, spec.n_data))
        )

    row_jobs = {"rows-7q": (spec7, *rows(spec7)), "rows-5q": (spec5, *rows(spec5))}
    bank_jobs = {"bank-a": (spec7, bank(spec7)), "bank-b": (spec7, bank(spec7))}

    def run_rows(tenant, spec, theta, data):
        sess = cluster.session(tenant)
        futs = [sess.submit(spec, theta[i], data[i]) for i in range(len(theta))]
        sess.drain()
        return jnp.stack([jnp.asarray(f.result(timeout=600)) for f in futs])

    def run_bank(tenant, spec, b):
        return cluster.session(tenant, bank_mode="implicit").executor(spec)(b)

    def serve_all():
        with ThreadPoolExecutor(4) as pool:
            futs = {t: pool.submit(run_rows, t, *job) for t, job in row_jobs.items()}
            futs |= {t: pool.submit(run_bank, t, *job) for t, job in bank_jobs.items()}
            return {t: f.result() for t, f in futs.items()}

    got, wall = timed(serve_all)
    errs = {}
    for t, (spec, theta, data) in row_jobs.items():
        errs[t] = max_err(got[t], reference_fidelities(spec, theta, data))
    for t, (spec, b) in bank_jobs.items():
        mat = b.materialize()
        errs[t] = max_err(got[t], reference_fidelities(spec, mat.theta, mat.data))
    for t, e in errs.items():
        check(f"serve {t}", e, FID_TOL)
    summary = assert_no_faults(cluster)
    log(
        f"serve: tenants={len(errs)} rows/tenant={SERVE_ROWS} "
        f"samples/bank={SERVE_SAMPLES} kernel=rows+{mode_of(spec7, SERVE_SAMPLES)} "
        f"max_abs_err={max(errs.values()):.3e} (tol {FID_TOL:.0e}) wall_s={wall:.3f}"
    )
    log(
        "serve: telemetry "
        + " ".join(
            f"{k}={summary[k]}"
            for k in ("total_completed", "batches", "fused_launches",
                      "multibank_launches", "banks_per_launch")
            if k in summary
        )
        + " failed_futures=0 dispatcher_errors=0 retries=0 migrations=0"
    )


def spill_phase(cluster) -> None:
    from repro.core import circuits, shift_rule
    from repro.kernels.vqc_statevector import shift_execution_info

    spec = circuits.build_quclassi_circuit(SPILL_QUBITS, SPILL_LAYERS)
    info = shift_execution_info(spec, SPILL_SAMPLES)
    if info["mode"] != "spill":
        raise AssertionError(f"expected the spill mode, got {info}")
    rng = np.random.default_rng(SEED + 2)
    b = shift_rule.build_shift_bank(
        uniform(rng, (spec.n_theta,)), uniform(rng, (SPILL_SAMPLES, spec.n_data))
    )
    backend = cluster.backend("batched", spec, n_workers=1)
    flat, wall = timed(backend.run_bank, b)
    grid = np.asarray(flat).reshape(b.n_groups, SPILL_SAMPLES)

    p = spec.n_theta
    groups = (0, 1, p // 2, p, p + 1, 2 * p)  # base, shifts in the first,
    lanes = (0, 1, SPILL_SAMPLES // 2, SPILL_SAMPLES - 1)  # middle, last tile
    descs = shift_rule.group_descriptors(p, False)
    theta_rows, data_rows = [], []
    for g in groups:
        j, s = descs[g]
        for lane in lanes:
            t = b.theta[lane]
            theta_rows.append(t if j < 0 else t.at[j].add(s))
            data_rows.append(b.data[lane])
    want = reference_fidelities(spec, jnp.stack(theta_rows), jnp.stack(data_rows))
    got = np.asarray([grid[g, lane] for g in groups for lane in lanes])
    err = max_err(got, want)
    check("spill", err, FID_TOL)
    log(
        f"spill: {SPILL_QUBITS}q/{SPILL_LAYERS}l m={(SPILL_QUBITS - 1) // 2} "
        f"samples={SPILL_SAMPLES} groups={b.n_groups} kernel={mode_of(spec, SPILL_SAMPLES)} "
        f"tiles={info['n_tiles']} checked={len(got)} lanes max_abs_err={err:.3e} "
        f"(tol {FID_TOL:.0e}) wall_s={wall:.3f}"
    )


def multichip_phase(n_chips: int) -> None:
    from repro.api import ClusterConfig, QuantumCluster, ServingConfig
    from repro.configs.quclassi_paper import get_quclassi
    from repro.core import shift_rule
    from repro.kernels import ops as kops

    spec = get_quclassi(CONFIG).spec
    rng = np.random.default_rng(SEED + 3)

    def bank(n):
        return shift_rule.build_shift_bank(
            uniform(rng, (spec.n_theta,)), uniform(rng, (n, spec.n_data))
        )

    one = bank(512)
    bank_set = [bank(n) for n in (128, 200, 77)]

    def single_device(b):  # the one-device kernel, on the default device
        return kops.vqc_fidelity_shiftbank(spec, b.theta, b.data)

    want_one = np.asarray(single_device(one))
    want_set = [np.asarray(single_device(b)) for b in bank_set]

    spilling = QuantumCluster(
        ClusterConfig(serving=ServingConfig(mode="async", worker_vmem_bytes=1))
    )
    try:
        # dispatcher path: every batch is over the per-worker VMEM model,
        # so each one spills onto the whole-device mesh
        def tenant(i):
            return spilling.session(f"tenant-{i}", bank_mode="implicit").executor(spec)

        def dispatcher_bank(b):
            return tenant(0)(b)

        def dispatcher_set(bs):  # one tenant per bank, submitting at once
            with ThreadPoolExecutor(len(bs)) as pool:
                futs = [pool.submit(tenant(i), b) for i, b in enumerate(bs)]
                return [f.result() for f in futs]

        paths = {}
        for kind in ("sharded", "mesh_spill"):
            backend = spilling.backend(kind, spec)
            if backend.cost_model().n_shards != n_chips:
                raise AssertionError(f"{kind}: mesh is not {n_chips} devices wide")
            paths[kind] = (backend.run_bank, backend.run_bank_set)
        paths["dispatcher_spill"] = (dispatcher_bank, dispatcher_set)

        for name, (run_one, run_set) in paths.items():
            got_one, t_one = timed(run_one, one)
            got_set, t_set = timed(run_set, bank_set)
            e_one = max_err(got_one, want_one)
            e_set = max(max_err(g, w) for g, w in zip(got_set, want_set))
            check(f"{name} bank", e_one, SHARD_TOL)
            check(f"{name} bank set", e_set, SHARD_TOL)
            log(
                f"multichip: {name} devices={n_chips} kernel={mode_of(spec, 512)} "
                f"bank max_abs_err={e_one:.3e} set max_abs_err={e_set:.3e} "
                f"(tol {SHARD_TOL:.0e} vs one device) wall_s={t_one:.3f}+{t_set:.3f}"
            )
        summary = assert_no_faults(spilling)
        if not summary.get("mesh_spills"):
            raise AssertionError("dispatcher spill path never reached the mesh")
        log(
            f"multichip: dispatcher mesh_spills={summary['mesh_spills']} "
            f"spilled_lanes={summary['spilled_lanes']}"
        )
    finally:
        spilling.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        default=1,
        choices=(1, 4),
        help="4: run only the multi-chip paths, on a 4-device mesh",
    )
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {SRC}; run it from a checkout")
    devices = require_tpu(args.chips)

    from repro.api import ClusterConfig, QuantumCluster, ServingConfig
    from repro.kernels.vqc_statevector import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(
        f"device: {devices[0].device_kind} x{len(devices)}; "
        f"Pallas interpret={resolve_interpret(None)}; "
        f"compile cache {cache} ({n_cached} entries at start)"
    )
    t0 = time.perf_counter()
    if args.chips == 4:
        multichip_phase(args.chips)
    else:
        cluster = QuantumCluster(ClusterConfig(serving=ServingConfig(mode="async")))
        try:
            train_phase(cluster)
            serve_phase(cluster)
            spill_phase(cluster)
        finally:
            cluster.close()
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
