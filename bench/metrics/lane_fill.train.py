"""Share of the kernel lanes paid for in the window that carried a real
circuit (the coalescer's ``batched_circuits`` over ``padded_lanes``)."""


def read(ctx):
    tel = ctx.result["telemetry"]
    return 100.0 * tel["batched_circuits"] / tel["padded_lanes"] if tel["padded_lanes"] else None
