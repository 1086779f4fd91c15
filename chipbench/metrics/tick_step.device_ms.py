"""Fused tick on the device: device time per execution of the tick's
compiled program (``_tick_core``), from the profiler trace."""
import devtrace


def read(ctx):
    if ctx.dev is None:
        return None
    hit = devtrace.program_time(ctx.dev, "tick_core")
    return 1e3 * hit[1] / hit[0] if hit and hit[0] else None
