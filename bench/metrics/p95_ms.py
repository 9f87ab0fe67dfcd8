"""95th percentile of the request latency over every request of the window."""
from bench.harness import percentile


def read(ctx):
    lat = ctx.result.get("latencies_s")
    return None if not lat else 1e3 * percentile(lat, 95)
