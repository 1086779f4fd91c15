"""Executor event loop: the surprise gate's time per scheduling tick, from
the program's ``surprise_gate`` spans inside the window (each completion's
predictive interval on the tick-start belief, and under a live event log
its PIT and ``observe``/``surprise`` events)."""


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    ticks = len(ctx.rec.of("tick_step", w.t_open, w.t_end))
    gates = ctx.rec.of("surprise_gate", w.t_open, w.t_end)
    if not ticks or not gates:
        return None
    return 1e3 * sum(s[2] - s[1] for s in gates) / ticks
