"""Closed-loop training through ``QuantumCluster``: each tenant calls
``Session.train`` back to back, one epoch over a seeded chunk of images per
call, in a thread of its own.  The traffic file sets the tenants, their
digit pairs, the bank mode, the batch, the steps per call and the rate.

Set-up builds the cluster once and drives it through two checked calls per
tenant, the same call the window makes: one of a single step (its loss and
the first gradient, read from the parameters after it) and one of a whole
chunk (its mean loss and the parameters' change).  Both also warm up the
window's shapes.  The window keeps every call's loss and parameters, and
``check`` samples some of them, drawn from the seed, beside the checked
calls: each ``Session.train`` call starts afresh from the parameters its
seed gives, so the reference can follow any one of them.  ``check`` runs
once the window has closed and the cluster is freed.
"""
from __future__ import annotations

import sys
import threading
import time

import numpy as np

from bench import digits, reference, workcount
from bench.cluster import cluster, qcfg, telemetry, telemetry_delta
from bench.harness import sub_seed

WINDOW_SPAN = "bench.window"


def _call(state, sess, images, labels, seed):
    """One ``Session.train`` call; returns its report."""
    import jax

    tr = state["traffic"]
    with jax.profiler.TraceAnnotation("bench.train_call"):
        return sess.train(
            state["qcfg"],
            (images, labels),
            state["test"],
            epochs=1,
            batch_size=tr["batch"],
            lr=tr["lr"],
            seed=seed,
        )


def _each_tenant(state, fn):
    """Run ``fn(tenant_index)`` in one thread per tenant; re-raise the
    first failure."""
    errors = []

    def run(i):
        try:
            fn(i)
        except BaseException as exc:  # reported by the caller's thread
            errors.append(exc)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(len(state["sessions"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def checked_inputs(cfg: dict, tr: dict, seed: int) -> list[dict]:
    """Per tenant, the rows and seeds of its two checked calls: one step
    on ``batch`` images, then a whole chunk; no row is used twice."""
    rng = np.random.default_rng(sub_seed(seed, 5))
    pairs = tr["digit_pairs"]
    per_call = tr["batch"] * tr["steps_per_call"]
    return [
        {
            "one": digits.pair_set(*pairs[i % len(pairs)], tr["batch"], rng),
            "chunk": digits.pair_set(*pairs[i % len(pairs)], per_call, rng),
            "seed_one": sub_seed(seed, 2, i),
            "seed_chunk": sub_seed(seed, 3, i),
        }
        for i in range(tr["tenants"])
    ]


def setup(cfg: dict, tr: dict, seed: int, seconds: float) -> dict:
    from repro.api import TenantPolicy

    rng = np.random.default_rng(sub_seed(seed, 1))
    cl = cluster(cfg, tr)
    policy = TenantPolicy(**tr["policy"])
    sessions = [
        cl.session(f"tenant-{i}", policy, bank_mode=tr["bank_mode"])
        for i in range(tr["tenants"])
    ]
    per_call = tr["batch"] * tr["steps_per_call"]
    pairs = tr["digit_pairs"]
    state = {
        "cfg": cfg,
        "traffic": tr,
        "seed": seed,
        "qcfg": qcfg(cfg),
        "cluster": cl,
        "sessions": sessions,
        "test": digits.pair_set(*pairs[0], tr["test_images"], rng),
        # the window's chunks: a pool per tenant, cycled
        "pool": [
            [
                digits.pair_set(*pairs[i % len(pairs)], per_call, rng)
                for _ in range(tr["pool_chunks"])
            ]
            for i in range(tr["tenants"])
        ],
        "checked": checked_inputs(cfg, tr, seed),
        "call_seeds": [sub_seed(seed, 4, i) for i in range(tr["tenants"])],
    }

    def checked(i):
        c = state["checked"][i]
        rep = _call(state, sessions[i], *c["one"], c["seed_one"])
        c["out_one"] = (rep.epochs[0].loss, rep.params)
        rep = _call(state, sessions[i], *c["chunk"], c["seed_chunk"])
        c["out_chunk"] = (rep.epochs[0].loss, rep.params)

    before = telemetry(cl)
    _each_tenant(state, checked)
    state["checked_modes"] = telemetry_delta(before, telemetry(cl))
    for _ in range(tr["warmup_rounds"]):
        _each_tenant(
            state,
            lambda i: _call(
                state, sessions[i], *state["pool"][i][-1], state["call_seeds"][i]
            ),
        )
    return state


def window_work(cfg: dict, tr: dict, steps: int) -> dict:
    """``kernel_work`` of ``steps`` training steps: (banks, work of one
    bank) under the configuration's bank kernel.  Counted from the bank's
    shape alone, so it reads the same in every bank mode."""
    lanes = tr["batch"] * reference.patch_grid(cfg["image_size"]) ** 2
    bank = workcount.bank_work(
        cfg, lanes, 2 * cfg["n_theta"] + 1, spill_states=cfg.get("spill_states", 0)
    )
    return {cfg["bank_kernel"]: (steps * cfg["n_classes"], bank)}


def window(state: dict, seconds: float) -> dict:
    import jax

    tr, cfg = state["traffic"], state["cfg"]
    calls = [[] for _ in state["sessions"]]
    before = telemetry(state["cluster"])
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def tenant(i):
        k = 0
        while time.perf_counter() < deadline:
            images, labels = state["pool"][i][k % tr["pool_chunks"]]
            rep = _call(state, state["sessions"][i], images, labels, state["call_seeds"][i] + k)
            calls[i].append((rep.epochs[0].circuits_executed, rep.epochs[0].loss, rep.params))
            k += 1

    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        _each_tenant(state, tenant)
    t1 = time.perf_counter()
    after = telemetry(state["cluster"])
    steps = sum(len(c) for c in calls) * tr["steps_per_call"]
    state["calls"] = [[out for _, *out in c] for c in calls]
    return {
        "window_s": t1 - t0,
        "circuits": sum(n for c in calls for n, *_ in c),
        "steps": steps,
        "calls": sum(len(c) for c in calls),
        "kernel_work": window_work(cfg, tr, steps),
        "telemetry": telemetry_delta(before, after),
    }


def close(state: dict) -> None:
    state["cluster"].close()
    state.pop("cluster")
    state.pop("sessions")


# ---------------------------------------------------------------- checking
def _leaf_gaps(prog: dict, ref: dict) -> dict:
    """Per leaf: |norm(program) - norm(reference)| over the larger of the
    reference leaf's norm and the median reference leaf's norm.  Leaves
    whose reference norm is under a thousandth of the median are left out
    (rounding alone moves them)."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    out = {}
    for k, v in prog.items():
        if norms[k] < 1e-3 * med:
            continue
        gap = abs(float(np.linalg.norm(np.asarray(v, np.float64))) - norms[k])
        out[k] = gap / max(norms[k], med)
    return out


def readings(cfg: dict, tr: dict, checked: list[dict], dtype=None, fault=None) -> dict:
    """The four numbers ``correct`` compares, worst over the calls checked.

    Each entry of ``checked`` is a ``Session.train`` call: its rows and
    seed, and the program's (mean loss, parameters) after it.  A call of
    one step (``one``) gives ``loss_step1`` and ``grad_step1``; a call of
    a whole chunk (``chunk``: set-up's, or one sampled from the window)
    gives ``loss_chunk`` and ``change_chunk``.  The reference follows the
    same steps.  ``dtype`` / ``fault`` put the reference, in lower
    precision or with a planted fault, in the program's place (the control
    and the fault readings)."""
    import jax.numpy as jnp

    ops = reference.circuit_of(cfg)
    worst = {"loss_step1": 0.0, "loss_chunk": 0.0, "grad_step1": 0.0, "change_chunk": 0.0}
    kw = dict(batch=tr["batch"], lr=tr["lr"])
    lower = dict(dtype=dtype or jnp.float32, half_batch=fault == "half_batch")
    for c in checked:
        now = {}
        for part, steps in (("one", 1), ("chunk", tr["steps_per_call"])):
            if part not in c:
                continue
            seed = c[f"seed_{part}"]
            p0 = reference.params_of(cfg, seed)
            ref_ls, ref_g, ref_p = reference.sgd_trajectory(
                ops, p0, *c[part], seed=seed, steps=steps, **kw
            )
            if dtype is None and fault is None:
                loss, p1 = c[f"out_{part}"]
            else:
                ls, _, p1 = reference.sgd_trajectory(
                    ops, p0, *c[part], seed=seed, steps=steps, **kw, **lower
                )
                loss = float(np.mean(ls))
                if fault == "unchanged":
                    p1 = p0
            ref_loss = float(np.mean(ref_ls))
            if part == "one":
                grad = {k: (np.asarray(p0[k]) - np.asarray(p1[k])) / tr["lr"] for k in p0}
                now["loss_step1"] = abs(loss - ref_loss) / abs(ref_loss)
                now["grad_step1"] = max(_leaf_gaps(grad, ref_g).values())
            else:
                change = {k: np.asarray(p1[k]) - np.asarray(p0[k]) for k in p0}
                ref_change = {k: np.asarray(ref_p[k]) - np.asarray(p0[k]) for k in p0}
                now["loss_chunk"] = abs(loss - ref_loss) / abs(ref_loss)
                now["change_chunk"] = max(_leaf_gaps(change, ref_change).values())
        worst = {k: max(worst[k], now.get(k, 0.0)) for k in worst}
    return worst


def window_sample(state: dict, seed: int) -> list[dict]:
    """``checked_window_calls`` calls of each tenant's window, drawn from
    the seed, as ``readings`` entries."""
    tr = state["traffic"]
    rng = np.random.default_rng(sub_seed(seed, 6))
    out = []
    for i, calls in enumerate(state["calls"]):
        n = min(tr["checked_window_calls"], len(calls))
        for k in sorted(rng.choice(len(calls), size=n, replace=False)):
            out.append({
                "chunk": state["pool"][i][k % tr["pool_chunks"]],
                "seed_chunk": state["call_seeds"][i] + int(k),
                "out_chunk": calls[k],
            })
    return out


def check(state: dict, result: dict, limits: dict) -> tuple[int, int, list]:
    sample = window_sample(state, state["seed"])
    nums = readings(state["cfg"], state["traffic"], state["checked"] + sample)
    for name, tel in (("set-up's checked calls", state["checked_modes"]),
                      ("window", result["telemetry"])):
        print(
            f"modes: {name}: launches {tel['launch_modes']} fused_launches="
            f"{tel['fused_launches']} multibank_launches={tel['multibank_launches']} "
            f"fused_banks={tel['fused_banks']} mesh_spills={tel['mesh_spills']}",
            file=sys.stderr,
            flush=True,
        )
    print(f"modes: window calls checked {len(sample)}", file=sys.stderr, flush=True)
    checks = [(k, v, limits[k]) for k, v in nums.items()]
    return result["calls"], 0, checks
