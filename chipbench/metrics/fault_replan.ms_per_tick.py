"""Planner on the fault path: time per scheduling tick in the program's
``replan`` spans, the frontier re-plans that a lost node or a failed
attempt causes (each holds its ``plan`` span)."""


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    replans = ctx.rec.of("replan", w.t_open, w.t_end)
    ticks = len(ctx.rec.of("tick_step", w.t_open, w.t_end))
    if not replans or not ticks:
        return None     # a program without replan spans, or no re-plan
    return 1e3 * sum(r[2] - r[1] for r in replans) / ticks
