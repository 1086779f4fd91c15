"""Executor event loop: mean time per tick outside the program's ``plan``
and ``tick_step`` spans (surprise gates, dispatch, heap, speculation)."""


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    steps = ctx.rec.of("tick_step", w.t_open, w.t_end)
    plans = ctx.rec.of("plan", w.t_open, w.t_end)
    total, n = 0.0, 0
    for a, b in zip(steps, steps[1:]):
        if a[3] != b[3]:
            continue
        inside = sum(p[2] - p[1] for p in plans if a[1] <= p[1] < b[1])
        total += (b[1] - a[1]) - (a[2] - a[1]) - inside
        n += 1
    return 1e3 * total / n if n else None
