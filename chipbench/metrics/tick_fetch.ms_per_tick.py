"""Tick engine: time per scheduling tick of the copies of the estimates
(``mean``, ``std``) to the host and their float64 widening, from the
program's ``tick_fetch`` spans (nested in ``tick_step``, so upload and
dispatch are ``tick_step.span_ms`` minus this)."""


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    ticks = len(ctx.rec.of("tick_step", w.t_open, w.t_end))
    fetches = ctx.rec.of("tick_fetch", w.t_open, w.t_end)
    if not ticks or not fetches:
        return None
    return 1e3 * sum(s[2] - s[1] for s in fetches) / ticks
