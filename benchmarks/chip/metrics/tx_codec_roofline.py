"""The codec transform kernel's share of its roofline in %: the bytes its
launches in the traced windows move (frames and noise read, decoded frames
written) and the FLOPs they need, counted from each launch's shapes
(``chipbench/kernel_work.py``), against the chip's HBM bandwidth and peak
FLOP/s, over the time the launches took.  Listed for the 1080p cell, where
the kernel's operands live in HBM."""
from chipbench import kernel_work

SILENT_WHEN = "the kernel is off the served path"


def read(ctx):
    return kernel_work.roofline_pct("tx_codec", kernel_work.TX_CODEC,
                                    ctx.trace, ctx.peaks)
