"""Tick engine: mean host time of the program's ``write_back`` span, the
fold of a run's final state into its estimator at the run's end (the copy
of the scalar models' leaves from the device, the touched rows' models
built from it, the bias statistics from the engine's host copy)."""


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    spans = ctx.rec.of("write_back", w.t_open, w.t_end)
    if not spans:
        return None     # a program without write_back spans
    return 1e3 * sum(s[2] - s[1] for s in spans) / len(spans)
