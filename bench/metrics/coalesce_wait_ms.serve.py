"""Mean wait of a circuit in the gateway's coalescer during the window, from
the program's ``coalesce_wait`` stage histogram (exact sum and count)."""


def read(ctx):
    total, count = ctx.result["telemetry"]["stages"].get("coalesce_wait", (0.0, 0))
    return 1e3 * total / count if count else None
