"""Readings of the control and of planted faults, for setting a cell's limits.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] [--seconds <s>]

The control is the plain reference put in the program's place and computed
one precision lower than the configuration states (bfloat16 amplitudes for
float32).  For a training cell the planted faults are the reference with
half of each batch left out and a step that leaves the parameters as they
were.  Each reading is made on the cell's own inputs at the cell's own
size, built from each seed exactly as a run builds them; one JSON line per
seed and reading.  A limit lies above what sound runs of the program read
and below the least of these.  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def readings(cfg: dict, tr: dict, seed: int, seconds: float) -> list[tuple[str, dict]]:
    import jax.numpy as jnp

    kind = harness.kind(tr["kind"])
    if tr["kind"] == "serve":
        inputs = kind.inputs(cfg, tr, seed, seconds)
        served = [True] * len(inputs[0])
        return [("control", kind.readings(cfg, inputs, served, dtype=jnp.bfloat16))]
    checked = kind.checked_inputs(cfg, tr, seed)
    return [
        ("control", kind.readings(cfg, tr, checked, dtype=jnp.bfloat16)),
        ("half_batch", kind.readings(cfg, tr, checked, fault="half_batch")),
        ("unchanged", kind.readings(cfg, tr, checked, fault="unchanged")),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    bm = harness.benchmark()
    cell = harness.workload(bm, args.workload)
    cfg = harness.config(cell["config"])
    tr = harness.traffic(cell["traffic"])
    harness.require_devices(cell["chips"])
    seconds = args.seconds or bm["run_seconds"]
    for seed in args.seeds:
        for kind, nums in readings(cfg, tr, seed, seconds):
            print(json.dumps({"workload": cell["name"], "seed": seed, "reading": kind, **nums}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
