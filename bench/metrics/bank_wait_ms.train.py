"""Mean host time of one class bank, from its first admission to its gathered
fidelities, as the trainer waits on the serving path (gateway, coalescer,
dispatcher and kernels below it): the program span ``trainer.bank``.
Exact sum and count, window deltas; nothing to read when the program
records no ``trainer.step``."""


def read(ctx):
    stages = ctx.result["telemetry"]["stages"]
    if not stages.get("trainer.step", (0.0, 0))[1]:
        return None
    total, count = stages.get("trainer.bank", (0.0, 0))
    return 1e3 * total / count if count else None
