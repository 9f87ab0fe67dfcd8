"""Implicit banks per fused shift-bank launch in the window (cross-tenant
multi-bank fusion: 1 means none)."""


def read(ctx):
    tel = ctx.result["telemetry"]
    return tel["fused_banks"] / tel["fused_launches"] if tel["fused_launches"] else None
