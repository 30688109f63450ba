"""The HBM bytes and FLOPs of one launch of each fleet kernel, from the
shapes in the launch's own HLO text, and the launches a trace holds.

Bytes are what the kernel's blocks move between HBM and the core: its
input operands as the grid reads them (``edge_motion``: both frames of
every pair, each band with its one-row halo, every lane with its halo
column) and its output.  FLOPs are the arithmetic the transform needs per
pixel; the 0/1 selection and pooling matmuls that move data inside the
kernel are not counted (``work.py``'s rule), so a kernel that drops them
reads the same work in less time.
"""
from __future__ import annotations

import re
from typing import List, Tuple

from chipbench.trace import OPS_LINE

# the launches, by the names the program gives them in the trace
EDGE_MOTION = r'^%edge_motion(\.\d+)? = .*custom_call_target="tpu_custom_call"'
TX_CODEC = r'^%encode_fleet(\.\d+)? = .*custom_call_target="tpu_custom_call"'

# edge_motion per pixel of a frame pair: per frame, Sobel gx and gy (3
# multiply-adds and a subtraction each: 7), the squared magnitude (3) and
# the threshold (1), so 18 twice; the XOR (1); the block sum (1)
EDGE_FLOPS_PER_PIXEL = 2 * 18 + 1 + 1
# tx_codec per pixel: quantize (multiply, round, divide), noise (multiply,
# add), clip (2).  The blur's window sums are left out: which resolution a
# camera takes is data, not shape, and they are under 1.1 per pixel.
TX_FLOPS_PER_PIXEL = 3 + 2 + 2

_SHAPE = re.compile(r"\b([a-z]+)(\d+)\[([\d,]*)\]")


def _shapes(op_text: str) -> List[Tuple[int, Tuple[int, ...]]]:
    """(element bytes, dims) of a launch's result then each operand, read
    from its HLO text up to its custom-call target."""
    head = op_text.split("custom_call_target", 1)[0]
    return [(int(bits) // 8, tuple(int(d) for d in dims.split(",") if d))
            for _, bits, dims in _SHAPE.findall(head)]


def _size(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def edge_motion_work(bands: Tuple[int, ...], out: Tuple[int, ...]
                     ) -> Tuple[float, float]:
    """(bytes, FLOPs) of one ``edge_motion`` launch whose banded frames are
    ``bands`` = (frames, T, TH+2, W+2) f32 and whose scores are ``out`` =
    (P, T, L, TH/bs, TW/bs) f32: P pairs read two haloed bands per row
    band, and every pixel of a pair's bands is worked once."""
    _, T, thp2, wp2 = bands
    P = out[0]
    read = 2 * P * T * thp2 * wp2 * 4
    flops = P * T * (thp2 - 2) * (wp2 - 2) * EDGE_FLOPS_PER_PIXEL
    return float(read + _size(out) * 4), float(flops)


def tx_codec_work(frames: Tuple[int, ...]) -> Tuple[float, float]:
    """(bytes, FLOPs) of one ``tx_codec`` launch over ``frames`` = (C, N, H,
    W) f32: frames and noise read, decoded frames written, three (C,)
    scalars."""
    C = frames[0]
    px = _size(frames)
    return float(3 * px * 4 + 3 * C * 4), float(px * TX_FLOPS_PER_PIXEL)


def launch_work(kernel: str, op_text: str) -> Tuple[float, float]:
    """(bytes, FLOPs) of one launch of ``kernel`` ('edge_motion' or
    'tx_codec') from its HLO text."""
    shapes = _shapes(op_text)
    if kernel == "edge_motion":
        (_, out), (_, bands) = shapes[0], shapes[1]
        return edge_motion_work(bands, out)
    (_, frames) = shapes[0]
    return tx_codec_work(frames)


def launches(trace, pattern: str) -> List[Tuple[str, int]]:
    """(HLO text, duration ns) of every operation in the traced window
    whose name matches ``pattern``, over every device."""
    rx = re.compile(pattern)
    w0, w1 = trace.window
    return [(name, d) for lines in trace.devices.values()
            for name, s, d in lines.get(OPS_LINE, [])
            if rx.search(name) and w0 <= s < w1]


def roofline_pct(kernel: str, pattern: str, trace, peaks) -> float:
    """The kernel's launches' share of their roofline in %: the least time
    the chip could take for their bytes and FLOPs (the larger of bytes
    over HBM bandwidth and FLOPs over peak FLOP/s) over the time they took.
    None when the trace holds no launch."""
    evs = launches(trace, pattern)
    secs = sum(d for _, d in evs) * 1e-9
    if not evs or secs <= 0:
        return None
    nbytes = flops = 0.0
    for text, _ in evs:
        b, f = launch_work(kernel, text)
        nbytes += b
        flops += f
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["flops_per_s"])
    return 100.0 * least / secs
