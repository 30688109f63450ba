"""The whole step's share of the chips' peak: detector forward FLOPs the
algorithm needs for the live cameras of the traced slots, over the traced
window, over chips x bf16 peak FLOP/s."""


def read(ctx):
    t = ctx.trace
    if t.window_s <= 0 or ctx.live_cam_slots <= 0:
        return None
    flops = ctx.work.step_flops(ctx.config, ctx.live_cam_slots)
    return 100.0 * flops / (t.window_s * ctx.chips * ctx.peaks["flops_per_s"])
