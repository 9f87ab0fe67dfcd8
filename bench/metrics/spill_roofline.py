"""The depth-tiled spill kernel's roofline share: the least time the chip
needs for the window's parameter-shift banks (bench/workcount.py, with the
HBM boundary states the configuration's ``spill_states`` counts) over the
device time of the spill path's two launches in the trace."""
from bench.metrics_common import roofline

#: the spill path's launches as the trace names them: the forward launch
#: and the double-buffered tile launch carry the names their ``pallas_call``
#: gives them, ``spill_forward`` and ``spill_tiles``, with XLA's numeric
#: suffix
PATTERN = r"^(ROOT )?%spill_(forward|tiles)(\.\d+)? = "


def read(ctx):
    return roofline(ctx, "spill", PATTERN)
