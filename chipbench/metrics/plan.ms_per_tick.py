"""Planner: HEFT (re-)plan time per scheduling tick, from the program's
``plan`` spans inside the window."""


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    ticks = len(ctx.rec.of("tick_step", w.t_open, w.t_end))
    plans = ctx.rec.of("plan", w.t_open, w.t_end)
    return 1e3 * sum(p[2] - p[1] for p in plans) / ticks if ticks else None
