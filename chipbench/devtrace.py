"""From a profiler trace to device busy time, per-program device time and
the idle gaps, named by the host span they fall in.

``load`` reads an ``.xplane.pb`` into plain lists: per TPU the executions
of each compiled program (the ``XLA Modules`` line), and the host spans
the benchmark annotated.  ``reduce`` works on those lists only, so a small
recorded trace (``tests/fixtures``) can check it without a chip.
"""
from __future__ import annotations

from pathlib import Path

#: the host span that delimits the traced window
WINDOW = "window"


def load(path, host_names) -> dict:
    """``{"devices": [[[program, start_ns, dur_ns], ...] per TPU],
    "host": [[span, start_ns, dur_ns], ...]}``; host spans are kept only
    when their name is in ``host_names``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices.append([[e.name, e.start_ns, e.duration_ns]
                                    for e in line.events])
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name in host_names)
    return {"devices": devices, "host": host}


def find_trace(log_dir) -> Path:
    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(name: str) -> str:
    """``jit__tick_core(5961134465585705950)`` -> ``jit__tick_core``."""
    return name.split("(", 1)[0]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(events: dict, bounds=None, top: int = 10) -> dict | None:
    """Busy and window seconds (busy averaged over the TPUs), device
    seconds and executions per program, and the top device programs and
    the idle time by the innermost host span it overlaps.  The traced window is the ``window`` host
    span, or, with ``bounds`` = (start, end) in seconds from that span's
    start, the part of it the measurement counted.  None when the trace
    holds no window or no device execution."""
    win = [h for h in events["host"] if h[0] == WINDOW]
    devices = [d for d in events["devices"] if d]
    if not win or not devices:
        return None
    w0 = min(h[1] for h in win)
    w1 = max(h[1] + h[2] for h in win)
    if bounds is not None:
        w0, w1 = w0 + bounds[0] * 1e9, w0 + bounds[1] * 1e9
    spans = sorted((h for h in events["host"] if h[0] != WINDOW),
                   key=lambda h: h[1])
    busy_total, programs, idle = 0.0, {}, {}
    for execs in devices:
        inside = [e for e in execs if e[1] < w1 and e[1] + e[2] > w0]
        for name, s, d in inside:
            p = programs.setdefault(program_name(name), [0, 0.0])
            p[0] += 1
            p[1] += d * 1e-9
        busy = _union((max(s, w0), min(s + d, w1)) for _, s, d in inside)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        for name, t in _split(gaps, _segments(spans, w0, w1)):
            idle[name] = idle.get(name, 0.0) + t * 1e-9
    n = len(devices)
    for p in programs.values():
        p[0] //= n
        p[1] /= n
    by_time = sorted(programs.items(), key=lambda kv: -kv[1][1])
    return {"busy_s": busy_total * 1e-9 / n, "window_s": (w1 - w0) * 1e-9,
            "programs": programs,
            "breakdown": {
                "device_ops": [[k, v[1]] for k, v in by_time[:top]],
                "idle_gaps": [[k, v / n] for k, v in
                              sorted(idle.items(), key=lambda kv: -kv[1])
                              [:top]]}}


def _segments(spans, w0, w1) -> list[tuple]:
    """[w0, w1] cut into (start, end, innermost host span) pieces; spans
    of one thread nest, so a stack of open spans gives the innermost."""
    bounds = sorted({w0, w1} | {t for _, s, d in spans for t in (s, s + d)
                                if w0 < t < w1})
    out, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(spans) and spans[i][1] <= a:
            stack.append(spans[i])
            i += 1
        stack = [sp for sp in stack if sp[1] + sp[2] > a]
        out.append((a, b, stack[-1][0] if stack else "outside spans"))
    return out


def _split(gaps, segments):
    """(span name, ns) for every overlap of a gap with a segment; both
    lists are sorted and free of overlaps within themselves."""
    j = 0
    for a, b in gaps:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            lo, hi = max(a, segments[k][0]), min(b, segments[k][1])
            if hi > lo:
                yield segments[k][2], hi - lo
            k += 1


def program_time(reduced: dict, fragment: str):
    """(executions, device seconds) of the programs whose name holds
    ``fragment``, or None when none ran."""
    hits = [v for k, v in reduced["programs"].items() if fragment in k]
    if not hits:
        return None
    return sum(h[0] for h in hits), sum(h[1] for h in hits)
