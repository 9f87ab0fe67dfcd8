"""Mean host time of the dense layer's gradient in one training step (the
``jax.grad`` of the data-encoding path): the program span
``trainer.dense_grad``.
Exact sum and count, window deltas; nothing to read when the program
records no ``trainer.step``."""


def read(ctx):
    stages = ctx.result["telemetry"]["stages"]
    if not stages.get("trainer.step", (0.0, 0))[1]:
        return None
    total, count = stages.get("trainer.dense_grad", (0.0, 0))
    return 1e3 * total / count if count else None
