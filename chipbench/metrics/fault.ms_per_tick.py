"""Executor fault path: time per scheduling tick in the program's
``fault`` spans (a lost node's or a failed attempt's censoring, retry and
back-off) less the ``replan`` spans inside them, which
``fault_replan.ms_per_tick`` reads."""
import bisect


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    faults = ctx.rec.of("fault", w.t_open, w.t_end)
    if not faults:
        return None     # a program without fault spans, or no fault
    ticks = len(ctx.rec.of("tick_step", w.t_open, w.t_end))
    if not ticks:
        return None
    # top-level fault spans do not overlap: the one holding a re-plan is
    # the last to start at or before it
    faults.sort(key=lambda s: s[1])
    starts = [s[1] for s in faults]
    total = sum(s[2] - s[1] for s in faults)
    for r in ctx.rec.of("replan", w.t_open, w.t_end):
        k = bisect.bisect_right(starts, r[1]) - 1
        if k >= 0 and r[2] <= faults[k][2] and r[3] == faults[k][3]:
            total -= r[2] - r[1]
    return 1e3 * total / ticks
