"""Device time of the codec transform kernel (``kernels/tx_codec``) per slot
served in the traced windows.

Its share of an HBM roofline is not reported: XLA keeps the kernel's frames,
noise and output in the core's vector memory (``S(1)`` layouts in the
trace), so bytes over HBM bandwidth is no bound on it, and the v5e's
vector-memory bandwidth is not in the published peaks."""

# None leaves the metric out of the line, with a note on stderr, rather than
# failing the run: a program that takes the kernel off the path has nothing
# for it to read, and step_mfu and episode_device_ms_per_slot still bound
# the step
SILENT_WHEN = "the kernel is off the served path"

# the kernel's launch carries the name of the jitted wrapper around it
KERNEL = r'^%encode_fleet(\.\d+)? = .*custom_call_target="tpu_custom_call"'


def read(ctx):
    s = ctx.trace.op_s(KERNEL)
    if not s or ctx.slots <= 0:
        return None
    return 1e6 * s / ctx.chips / ctx.slots
