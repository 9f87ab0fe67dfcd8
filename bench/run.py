"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix,
limits and metric readers are files under ``bench/`` found by name (see
``bench/harness.py``).  The run builds everything from ``--seed``, warms up
the cell's shapes (set-up, timed from process start), measures for
``--seconds``, then checks what the measured path produced against the
plain reference (``bench/reference.py``).  With ``--trace 1`` the window
runs under the JAX profiler and the line carries the per-layer metrics;
with ``--trace 0`` it carries the end-to-end ones.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, last, ``checks``:
each number compared beside its limit, which standard error also lists as
its last lines.  The run exits non-zero, printing no result, without a TPU
or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be non-negative")

    bm = harness.benchmark()
    cell = harness.workload(bm, args.workload)
    cfg = harness.config(cell["config"])
    tr = harness.traffic(cell["traffic"])
    lim = harness.limits(cell["name"])
    kind = harness.kind(tr["kind"])
    harness.use_program()
    devices = harness.require_devices(cell["chips"])
    harness.enable_compile_cache()
    line, checks = run_cell(
        cell, cfg, tr, lim, kind, devices, args, harness.cell_metrics(bm, cell["name"], bool(args.trace))
    )
    harness.print_result(line, checks)
    return 0


def run_cell(cell, cfg, tr, lim, kind, devices, args, metric_specs):
    """Set up, measure, check and read the metrics of one run; returns the
    result line (without its checks) and the checks."""
    compiles = harness.CompileCounter()
    state = kind.setup(cfg, tr, args.seed, args.seconds)
    setup_s = time.perf_counter() - T_START
    before = compiles.snapshot()
    trace_dir = None
    if args.trace:
        import jax

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python calls
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    result = kind.window(state, args.seconds)
    trace = None
    if args.trace:
        import jax

        from bench import trace_reduce

        jax.profiler.stop_trace()
        trace = trace_reduce.reduce_trace(trace_dir, kind.WINDOW_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
    in_window = [a - b for a, b in zip(compiles.snapshot(), before)]
    print(
        f"window: {result['window_s']:.3f}s circuits={result['circuits']} "
        f"programs_fetched={in_window[0]} compiled={in_window[1]} "
        f"loaded_from_disk={in_window[2]}",
        file=sys.stderr,
        flush=True,
    )
    memory = harness.memory_peak_bytes(devices)
    kind.close(state)
    t_check = time.perf_counter()
    attempted, failed, checks = kind.check(state, result, lim)
    print(f"check: {time.perf_counter() - t_check:.3f}s", file=sys.stderr, flush=True)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory,
    }
    ctx = SimpleNamespace(
        cell=cell,
        config=cfg,
        traffic=tr,
        chips=len(devices),
        setup_s=setup_s,
        result=result,
        trace=trace,
        peak=harness.peak(devices[0].device_kind),
    )
    line = {
        "correct": all(v <= limit for _, v, limit in checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": harness.read_metrics(metric_specs, ctx),
        "device": device,
    }
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
    return line, checks


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # worker threads of the program's runtime are closed; skip interpreter
    # teardown, which can wait on the profiler and the device client
    os._exit(code)
