"""Tick engine: mean host time of the program's ``tick_step`` span
(dispatch, device work, the copies of the estimates and bias statistics
to the host and their float64 widening)."""


def read(ctx):
    w = ctx.win
    spans = ctx.rec.of("tick_step", w.t_open, w.t_end)
    return 1e3 * sum(s[2] - s[1] for s in spans) / len(spans) if spans \
        else None
