"""Device time of the edge-motion kernel (``kernels/edge_motion``) per slot
served in the traced windows: ROIDet's moving-object pass (deepstream) and
reducto's keep decision both launch it.

Its share of an HBM roofline is not reported, for the reason
``tx_codec_us_per_slot`` gives: its operands sit in vector memory."""


# None leaves the metric out of the line, with a note on stderr, rather than
# failing the run: a program that takes the kernel off the path has nothing
# for it to read, and step_mfu and episode_device_ms_per_slot still bound
# the step
SILENT_WHEN = "the kernel is off the served path"

def kernel_pattern(width: int) -> str:
    """The kernel's launch has no name of its own in the trace; it is the
    Mosaic call whose inputs are row bands of frames with a one-pixel halo
    on each side (width + 2 columns)."""
    return (rf'custom-call\(f32\[\d+,\d+,\d+,{width + 2}\]'
            r'.*custom_call_target="tpu_custom_call"')


def read(ctx):
    s = ctx.trace.op_s(kernel_pattern(ctx.config["scene"]["width"]))
    if not s or ctx.slots <= 0:
        return None
    return 1e6 * s / ctx.chips / ctx.slots
