"""Closed-loop training through ``QuantumCluster`` at a register width
whose shift banks run on the depth-tiled spill kernel.

Set-up, the window and closing are ``bench/kinds/train.py``'s; the check
follows the same calls against ``bench/reference_wide.py``, which reads F
from the two m-qubit registers instead of simulating the whole circuit.
The window also counts the spill launches the program's telemetry
records (banks, depth tiles, HBM boundary bytes), which the ``modes:``
lines print beside the launch modes and ``spill_tiles_per_bank`` reads.
A program that does not count them cannot run this kind, and set-up
exits before it builds anything.
"""
from __future__ import annotations

import sys

import numpy as np

from bench import reference_wide as reference
from bench.kinds import train
from bench.kinds.train import (
    WINDOW_SPAN,
    checked_inputs,
    close,
    window_sample,
    window_work,
)

__all__ = ["WINDOW_SPAN", "checked_inputs", "close", "window_work",
           "setup", "window", "readings", "check"]

SPILL_COUNTERS = ("spill_banks", "spill_tiles", "spill_boundary_bytes")


def _spill(cl) -> dict:
    tel = cl.telemetry
    return {k: getattr(tel, k) for k in SPILL_COUNTERS}


def setup(cfg: dict, tr: dict, seed: int, seconds: float) -> dict:
    from repro.serve.metrics import Telemetry

    if not hasattr(Telemetry, "on_spill_launch"):
        raise SystemExit("bench: the program counts no spill launches; it cannot run this kind")
    state = train.setup(cfg, tr, seed, seconds)
    state["setup_spill"] = _spill(state["cluster"])
    return state


def window(state: dict, seconds: float) -> dict:
    before = _spill(state["cluster"])
    result = train.window(state, seconds)
    after = _spill(state["cluster"])
    result["spill"] = {k: after[k] - before[k] for k in SPILL_COUNTERS}
    return result


def readings(cfg: dict, tr: dict, checked: list[dict], dtype=None, fault=None) -> dict:
    """``train.readings`` against ``reference_wide``: the four numbers
    ``correct`` compares, worst over the calls checked; ``dtype`` /
    ``fault`` put the reference, in lower precision or with a planted
    fault, in the program's place."""
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
                now["grad_step1"] = max(train._leaf_gaps(grad, ref_g).values())
            else:
                change = {k: np.asarray(p1[k]) - np.asarray(p0[k]) for k in p0}
                ref_change = {k: np.asarray(ref_p[k]) - np.asarray(p0[k]) for k in p0}
                now["loss_chunk"] = abs(loss - ref_loss) / abs(ref_loss)
                now["change_chunk"] = max(train._leaf_gaps(change, ref_change).values())
        worst = {k: max(worst[k], now.get(k, 0.0)) for k in worst}
    return worst


def _modes(name: str, tel: dict, spill: dict) -> None:
    print(
        f"modes: {name}: launches {tel['launch_modes']} fused_launches="
        f"{tel['fused_launches']} multibank_launches={tel['multibank_launches']} "
        f"fused_banks={tel['fused_banks']} mesh_spills={tel['mesh_spills']} "
        + " ".join(f"{k}={v}" for k, v in spill.items()),
        file=sys.stderr,
        flush=True,
    )


def check(state: dict, result: dict, limits: dict) -> tuple[int, int, list]:
    sample = window_sample(state, state["seed"])
    nums = readings(state["cfg"], state["traffic"], state["checked"] + sample)
    # set-up's launch modes are its checked calls'; its spill counters
    # cover the whole of set-up, warm-up included
    _modes("set-up's checked calls", state["checked_modes"], state["setup_spill"])
    _modes("window", result["telemetry"], result["spill"])
    print(f"modes: window calls checked {len(sample)}", file=sys.stderr, flush=True)
    checks = [(k, v, limits[k]) for k, v in nums.items()]
    return result["calls"], 0, checks
