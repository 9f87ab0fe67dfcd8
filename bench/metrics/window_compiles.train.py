"""Programs compiled or loaded from the persistent cache inside the window
by the trainer and the data plane: the count of the program's ``compile``
counter (each compile recorded into the innermost open program span's
recorder).  0 when the window compiled nothing; nothing to read when the
program records no ``trainer.step``."""


def read(ctx):
    stages = ctx.result["telemetry"]["stages"]
    if not stages.get("trainer.step", (0.0, 0))[1]:
        return None
    return stages.get("compile", (0.0, 0))[1]
