"""Circuits whose fidelity reached the client, over the whole window (a
parameter-shift bank counts lanes x groups)."""


def read(ctx):
    return ctx.result["circuits"] / ctx.result["window_s"]
