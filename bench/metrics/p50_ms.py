"""Median request latency, scheduled send to all fidelities on the host;
a refused or failed request counts as infinitely late."""
from bench.harness import percentile


def read(ctx):
    lat = ctx.result.get("latencies_s")
    return None if not lat else 1e3 * percentile(lat, 50)
