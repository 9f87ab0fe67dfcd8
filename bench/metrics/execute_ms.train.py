"""Mean host time of one coalesced batch in the data plane (partition,
padding, kernel dispatch, per-member slices), in the thread that runs it:
the program span ``dispatcher.execute``.  Host time only: the batch's
device work may still run when the span closes.
Exact sum and count, window deltas; nothing to read when the program
records no ``trainer.step``."""


def read(ctx):
    stages = ctx.result["telemetry"]["stages"]
    if not stages.get("trainer.step", (0.0, 0))[1]:
        return None
    total, count = stages.get("dispatcher.execute", (0.0, 0))
    return 1e3 * total / count if count else None
