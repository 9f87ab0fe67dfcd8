"""Host time of one training step outside its class banks and its
dense-layer gradient: bank build, gradient assembly and the optimizer
update.  From the program spans ``trainer.step``, ``trainer.bank`` and
``trainer.dense_grad`` (exact sums and counts, window deltas); nothing to
read when the program records no ``trainer.step``."""


def read(ctx):
    stages = ctx.result["telemetry"]["stages"]
    total, count = stages.get("trainer.step", (0.0, 0))
    if not count:
        return None
    inner = sum(stages.get(k, (0.0, 0))[0] for k in ("trainer.bank", "trainer.dense_grad"))
    return 1e3 * (total - inner) / count
