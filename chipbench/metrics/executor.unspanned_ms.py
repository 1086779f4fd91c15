"""Executor event loop: mean time per tick that no span of the program
covers (dispatch, the event heap, speculation, the tick engine's host-side
packing).  Over the same consecutive ``tick_step`` pairs of one run as
``executor.self_ms``: the interval between their starts minus the union of
every program span inside it."""
import bisect

#: spans the harness enters around the program, not the program's own
HARNESS = ("run", "run_setup", "window")


def read(ctx):
    if ctx.kind != "executor":
        return None
    w = ctx.win
    if not ctx.rec.of("surprise_gate", w.t_open, w.t_end):
        return None     # a program without spans inside the tick
    steps = ctx.rec.of("tick_step", w.t_open, w.t_end)
    union = merge(s[1:3] for s in ctx.rec.spans if s[0] not in HARNESS)
    total, n = 0.0, 0
    for a, b in zip(steps, steps[1:]):
        if a[3] != b[3]:
            continue
        total += (b[1] - a[1]) - covered(union, a[1], b[1])
        n += 1
    return 1e3 * total / n if n else None


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(t0, t1)`` intervals as sorted disjoint intervals."""
    out: list[list[float]] = []
    for t0, t1 in sorted(intervals):
        if out and t0 <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t1)
        else:
            out.append([t0, t1])
    return [tuple(u) for u in out]


def covered(union, lo: float, hi: float) -> float:
    """How much of [lo, hi) the sorted disjoint intervals ``union`` cover."""
    k = max(bisect.bisect_right(union, (lo, float("inf"))) - 1, 0)
    got = 0.0
    for t0, t1 in union[k:]:
        if t0 >= hi:
            break
        got += max(0.0, min(t1, hi) - max(t0, lo))
    return got
