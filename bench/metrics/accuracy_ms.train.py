"""Mean host time of one accuracy pass of the trainer (two per epoch, train
and test set): the program span ``trainer.accuracy``.
Exact sum and count, window deltas; nothing to read when the program
records no ``trainer.step``."""


def read(ctx):
    stages = ctx.result["telemetry"]["stages"]
    if not stages.get("trainer.step", (0.0, 0))[1]:
        return None
    total, count = stages.get("trainer.accuracy", (0.0, 0))
    return 1e3 * total / count if count else None
