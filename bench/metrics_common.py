"""Arithmetic the metric readers share."""
from __future__ import annotations

from bench import trace_reduce, workcount


def device_idle(ctx):
    if not ctx.trace:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def _works(ctx, kernel: str):
    entry = ctx.result.get("kernel_work", {}).get(kernel)
    return entry if entry and entry[0] else None


def roofline(ctx, kernel: str, pattern: str):
    """100 x roofline seconds of the kernel's calls / its device seconds.
    ``kernel_work[kernel]`` is (calls, work of one call)."""
    entry = _works(ctx, kernel)
    if not ctx.trace or entry is None:
        return None
    device_s = trace_reduce.op_seconds_matching(ctx.trace, pattern)
    if device_s <= 0:
        return None
    calls, work = entry
    return 100.0 * calls * workcount.roofline_s(work, ctx.peak) / device_s


def mfu(ctx):
    ops = sum(calls * work["ops"] for calls, work in ctx.result.get("kernel_work", {}).values())
    if not ops:
        return None
    return 100.0 * ops / (ctx.result["window_s"] * ctx.chips * ctx.peak["flops_per_s"])
