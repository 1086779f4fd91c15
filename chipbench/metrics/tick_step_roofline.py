"""Fused tick against its roofline: the least time the chip needs to move
the bytes one tick must move (``roofline.tick_step_bytes``) at peak HBM
bandwidth, over the measured device time per tick, in percent.  Bytes
bound it: the tick does a few hundred operations per byte less than the
chip's balance point."""
import devtrace
import roofline


def read(ctx):
    if ctx.dev is None or ctx.peaks is None:
        return None
    hit = devtrace.program_time(ctx.dev, "tick_core")
    if not hit or not hit[0] or hit[1] <= 0:
        return None
    s = ctx.shape
    least = roofline.tick_step_bytes(s["T"], s["N"], s["Nb"], s["B"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (hit[1] / hit[0])
