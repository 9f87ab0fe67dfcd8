"""Benchmark driver: one section per paper table/figure.

  Fig 3/4 : runtime + circuits/sec vs workers, IBM-Q (uncontrolled env)
  Fig 5   : one client, controlled env (GCP), qubit-capped workers
  Fig 6   : 4 concurrent clients, heterogeneous workers, multi- vs
            single-tenant
  §IV-B   : accuracy, distributed vs non-distributed  (--full only: slow)
  extra   : fused-kernel + shift-bank microbenchmarks (beyond paper)

Every run emits machine-readable artifacts — ``BENCH_kernel.json`` (fused
kernel wall time + analytic traffic ratios, shift-bank gate-application and
angle-byte ratios), ``BENCH_gateway.json`` (coalescing throughput +
latency) and ``BENCH_federated.json`` (quorum vs barrier round throughput,
secure-aggregation parity, accuracy-vs-rounds) — so the perf trajectory is
tracked across PRs; CI uploads them.

Usage:  PYTHONPATH=src python -m benchmarks.run [--full | --quick]
                                                [--out-dir DIR]
"""
from __future__ import annotations

import argparse
import json
import os
import time


def section(title):
    print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")


def _write_artifact(out_dir: str, name: str, payload) -> None:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=float)
    print(f"[artifact] wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--full", action="store_true", help="include the slow accuracy training runs"
    )
    ap.add_argument(
        "--quick",
        action="store_true",
        help="CI smoke mode: kernel + gateway sections only, "
        "small batches, still emits BENCH_*.json",
    )
    ap.add_argument(
        "--out-dir",
        default=".",
        help="directory for BENCH_kernel.json / BENCH_gateway.json",
    )
    args = ap.parse_args()
    if args.full and args.quick:
        ap.error("--full and --quick are mutually exclusive")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()

    from benchmarks import gateway_throughput, kernel_bench

    if not args.quick:
        from benchmarks import multitenant, runtime_controlled, runtime_uncontrolled

        section("Fig 3 + Fig 4: IBM-Q backends (uncontrolled), runtime & c/s")
        runtime_uncontrolled.main()

        section("Fig 5: controlled environment (GCP), one client")
        runtime_controlled.main()

        section("Fig 6: multi-tenant system, 4 concurrent clients")
        multitenant.main()

    section(
        "Kernel microbenchmark: fused Pallas VQC + shift-structured "
        "banks (beyond paper)"
    )
    kernel_result = kernel_bench.main(quick=args.quick)
    _write_artifact(
        args.out_dir,
        "BENCH_kernel.json",
        {
            "wall_time_note": "CPU interpret-mode wall time; analytic ratios are "
            "the TPU-side signal",
            **kernel_result,
        },
    )

    if not args.quick:
        section("Noise-aware scheduling (beyond paper — the paper's §V limitation)")
        from benchmarks import noise_aware

        noise_aware.main()

    section("Serving gateway: cross-tenant circuit-bank coalescing (beyond paper)")
    gateway_result = gateway_throughput.main(
        run_kernel=args.full,
        scale=0.05 if args.quick else 0.25,
        trace_path=os.path.join(args.out_dir, "trace_gateway.json"),
    )
    _write_artifact(args.out_dir, "BENCH_gateway.json", gateway_result)

    section("Federated DQL: quorum rounds vs sync barrier (beyond paper)")
    from benchmarks import federated_bench

    federated_result = federated_bench.run(quick=not args.full)
    _write_artifact(args.out_dir, "BENCH_federated.json", federated_result)

    if args.full:
        from benchmarks import accuracy

        section("§IV-B accuracy: distributed vs non-distributed")
        accuracy.main()
    elif not args.quick:
        section(
            "§IV-B accuracy (skipped — pass --full; one-step gradient "
            "equivalence check only)"
        )
        from benchmarks import accuracy

        gap = accuracy.gradient_equivalence(1, 5)
        print(f"task 1/5: max |distributed - local| theta-grad = {gap:.2e}")

    print(f"\nall benchmarks done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
