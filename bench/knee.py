"""Sweep the offered rate of a serving cell to find its knee, once, on the chip.

    python3 bench/knee.py --config <name> --traffic <mix> --seed <n> --seconds <s> --rates <r> [<r> ...]

One process on one chip, one cluster: the mix's set-up runs once, then
each rate in turn is offered open-loop for ``--seconds`` with the mix
otherwise unchanged (the mix's own ``rate_per_s``, where it has one, only
paces the warm-up).  For each rate one JSON line: requests sent, p50 and
p95 latency, the completed rate, and the p95 of the first and last thirds
of the requests (a growing backlog shows as a last third far slower than
the first).  The knee is the highest rate whose p95 keeps within the
tenants' SLO with no growing backlog; the cell's traffic file then states
about four fifths of it.  The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cfg = harness.config(args.config)
    tr = {"rate_per_s": args.rates[0], **harness.traffic(args.traffic)}
    kind = harness.kind(tr["kind"])
    harness.use_program()
    harness.require_devices(1)
    harness.enable_compile_cache()
    state = kind.setup(cfg, tr, args.seed, args.seconds)
    try:
        for i, rate in enumerate(args.rates):
            mix = dict(tr, rate_per_s=rate)
            inputs = kind.inputs(cfg, mix, harness.sub_seed(args.seed, 100 + i), args.seconds)
            sched, done, _, t0 = kind.run_schedule(state, *inputs, span="bench.knee")
            lat = done - sched
            third = max(1, len(lat) // 3)
            ok = np.isfinite(done)
            span = max(float(done[ok].max()) - t0, args.seconds) if ok.any() else args.seconds
            print(json.dumps({
                "rate_per_s": rate,
                "requests": len(lat),
                "failed": int((~ok).sum()),
                "p50_ms": 1e3 * harness.percentile(lat, 50),
                "p95_ms": 1e3 * harness.percentile(lat, 95),
                "completed_per_s": float(ok.sum()) / span,
                "p95_first_third_ms": 1e3 * harness.percentile(lat[:third], 95),
                "p95_last_third_ms": 1e3 * harness.percentile(lat[-third:], 95),
            }), flush=True)
    finally:
        kind.close(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
