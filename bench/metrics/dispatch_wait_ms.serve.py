"""Mean time from coalesced batch to kernel dispatch during the window: the
``place_wait`` plus ``dispatch_lag`` stage means (co-Manager placement and
the dispatcher's hand-off to a worker slot)."""


def read(ctx):
    stages = ctx.result["telemetry"]["stages"]
    out = 0.0
    for name in ("place_wait", "dispatch_lag"):
        total, count = stages.get(name, (0.0, 0))
        if not count:
            return None
        out += total / count
    return 1e3 * out
