"""Depth tiles per bank of the window's spilled shift launches, from the
program's spill counters (``Telemetry.spill_tiles`` over ``spill_banks``):
the plan's tile count when each launch covers one bank."""


def read(ctx):
    spill = ctx.result.get("spill")
    if not spill or not spill["spill_banks"]:
        return None
    return spill["spill_tiles"] / spill["spill_banks"]
