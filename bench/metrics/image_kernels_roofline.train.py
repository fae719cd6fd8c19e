"""The image kernels' share of their roofline in the training cells:
the least time the chip needs for the bytes the render, grayscale and
resize must move for the lanes the pool served, at ``hbm_bytes_per_s``,
over the summed device time of those kernels' operations.

Per served lane: the render writes the 210 x 160 x 3 screen (100,800
B); grayscale reads it and writes 210 x 160 (33,600 B); the resize
reads that and writes 84 x 84 (7,056 B).  The algorithm's count, the
same whatever implements it; the kernels do no arithmetic worth
bounding them by."""

KERNELS = ("pong_render", "grayscale", "resize")
SCREEN = 210 * 160 * 3
GRAY = 210 * 160
FRAME = 84 * 84
LANE_BYTES = SCREEN + (SCREEN + GRAY) + (GRAY + FRAME)


def read(trace, counts):
    t = trace.kernel_s(*KERNELS)
    if t <= 0 or not counts.get("served"):
        return None
    least = counts["served"] * LANE_BYTES / counts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / t
