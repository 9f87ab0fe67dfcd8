"""The fused (multi-)bank shift kernel's roofline share: the least time the
chip needs for the window's parameter-shift banks (bench/workcount.py) over
the device time of that kernel's operations in the trace."""
from bench.metrics_common import roofline

#: the shift-bank kernel as the trace names it: an operation's name is its
#: HLO instruction's text, and the Mosaic call's instruction takes the name
#: of the jitted function around it, ``kernels.ops._shiftgroups_jit`` or
#: ``_shiftgroups_multibank_jit``, with XLA's numeric suffix
PATTERN = r"^(ROOT )?%_shiftgroups(_multibank)?_jit(\.\d+)? = "


def read(ctx):
    return roofline(ctx, "shiftbank", PATTERN)
