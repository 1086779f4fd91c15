"""Tick engine: time per scheduling tick of the copies of the three bias
statistics to the host after the tick, from the program's ``bias_fetch``
spans (outside ``tick_step``, so part of ``executor.self_ms``)."""


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    ticks = len(ctx.rec.of("tick_step", w.t_open, w.t_end))
    fetches = ctx.rec.of("bias_fetch", w.t_open, w.t_end)
    if not ticks or not fetches:
        return None
    return 1e3 * sum(s[2] - s[1] for s in fetches) / ticks
