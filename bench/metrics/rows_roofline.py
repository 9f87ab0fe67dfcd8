"""The row kernel's roofline share: the least time the chip needs for the
row circuits of the window (bench/workcount.py) over the device time of the
row kernel's operations in the trace."""
from bench.metrics_common import roofline

#: the row kernel as the trace names it: an operation's name is its HLO
#: instruction's text, and the Mosaic call's instruction takes the name of
#: the jitted function around it, ``kernels.ops.vqc_fidelity`` (or
#: ``vqc_p0``), with XLA's numeric suffix
PATTERN = r"^(ROOT )?%vqc_(fidelity|p0)(\.\d+)? = "


def read(ctx):
    return roofline(ctx, "rows", PATTERN)
