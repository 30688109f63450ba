"""The edge-motion kernel's share of its roofline in %: the bytes its
launches in the traced windows move (both haloed bands of every frame pair,
and the block scores) and the FLOPs they need, counted from each launch's
shapes (``chipbench/kernel_work.py``), against the chip's HBM bandwidth and
peak FLOP/s, over the time the launches took.  Listed for the 1080p cell,
where the kernel's operands live in HBM."""
from chipbench import kernel_work

SILENT_WHEN = "the kernel is off the served path"


def read(ctx):
    return kernel_work.roofline_pct("edge_motion", kernel_work.EDGE_MOTION,
                                    ctx.trace, ctx.peaks)
