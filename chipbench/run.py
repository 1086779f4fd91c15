"""Run one cell of the benchmark on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--control 1]

Set-up (imports, the cell's inputs made from the seed, loading or
compiling every program, one warm-up) is timed as ``setup_s``; then the
cell's runner works for ``--seconds`` while ticks are timed on the host
clock.  With ``--trace 1`` the window (at most ``TRACE_SECONDS``) runs
under the profiler and the per-layer metrics are printed instead.  Once
the window has closed and the program's state is freed, the plain
reference replays what the window absorbed and the numbers compared are
printed beside their limits.  ``--control 1`` puts the reference computed
in bfloat16 in the program's place in that comparison: such a run has to
come out not correct.

The last line of standard output is one JSON object.  Without a TPU, or
without the program beside the benchmark, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402
import harness  # noqa: E402
from harness import BenchError  # noqa: E402

#: longest window a --trace 1 run puts under the profiler
TRACE_SECONDS = 8.0
#: host spans kept from the trace to name the idle gaps
HOST_SPANS = ("window", "run", "run_setup", "plan", "tick_step", "tick")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT) -> int:
    args = parse(argv)
    try:
        return run(args, Path(root))
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1


def run(args, root: Path) -> int:
    bench, cell, conf = harness.find_cell(root, args.workload)
    bench_dir = root / bench["paths"][0]
    cfg = harness.load_json(root / conf["file"])
    traffic = harness.load_json(bench_dir / "traffic" / f"{cell['traffic']}.json")
    sys.path.insert(0, str(root / "src"))
    try:
        import repro  # noqa: F401
    except ImportError:
        raise BenchError("the program (src/repro) is not beside the "
                         "benchmark") from None
    t_import = time.perf_counter()
    devs = harness.device_gate(cell["chips"])
    harness.enable_cache()
    import jax

    t_devices = time.perf_counter()
    runner = harness.load_module(bench_dir / "runners" / f"{cfg['runner']}.py")
    rec = harness.Recorder(annotate=bool(args.trace))
    with harness.count_compiles() as setup_compiles:
        drv = runner.Runner(cfg, traffic, args.seed, rec)
    win = harness.Window(min(args.seconds, TRACE_SECONDS) if args.trace
                         else args.seconds)
    log_dir = bench_dir / "out" / "trace" / args.workload
    t_runner = time.perf_counter()
    gc.collect()        # the window starts from a collected heap
    t_collect = time.perf_counter()
    if args.trace:
        _clear(log_dir)
        jax.profiler.start_trace(str(log_dir), profiler_options=_profile_options())
    setup_s = time.perf_counter() - T_START
    print(f"chipbench: setup_s {setup_s!r}: imports {t_import - T_START!r}, "
          f"devices {t_devices - t_import!r}, runner "
          f"{t_runner - t_devices!r}, full collection "
          f"{t_collect - t_runner!r} over "
          f"{len(gc.get_objects())} objects; {setup_compiles[0]} programs, "
          f"{setup_compiles[1]} from the compile cache", file=sys.stderr,
          flush=True)
    with harness.count_compiles() as compiles, harness.gc_passes() as gcs:
        with rec.span("window"):
            win.open()
            drv.run_window(win)
    if args.trace:
        jax.profiler.stop_trace()
    lat, n_obs = drv.ticks(win)
    q = (statistics.quantiles(lat, n=100) if len(lat) > 1 else lat * 99)
    print(f"chipbench: window {win.length!r} s, {len(lat)} ticks, ms at "
          f"p50 {1e3 * q[49]:.3f} p90 {1e3 * q[89]:.3f} p99 {1e3 * q[98]:.3f} "
          f"max {1e3 * max(lat, default=0.0):.3f}; {compiles[0]} programs "
          f"prepared; {len(gcs)} full collections, longest "
          f"{1e3 * max(gcs, default=0.0):.3f} ms", file=sys.stderr, flush=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}

    ctx = SimpleNamespace(kind=drv.kind, shape=drv.shape, rec=rec, win=win,
                          lat=lat, n_obs=n_obs, compiles=compiles[0],
                          dev=None, peaks=None)
    breakdown = None
    if args.trace:
        ctx.peaks = harness.peaks_for(devs[0].device_kind)
        t_win = next(sp[1] for sp in rec.spans if sp[0] == "window")
        ctx.dev = devtrace.reduce(
            devtrace.load(devtrace.find_trace(log_dir), HOST_SPANS),
            bounds=(win.t_open - t_win, win.t_end - t_win))
        if ctx.dev is not None:
            device["busy_s"] = ctx.dev["busy_s"]
            device["window_s"] = ctx.dev["window_s"]
            breakdown = ctx.dev["breakdown"]
        metrics = {}
        for m in harness.metrics_for(bench, args.workload, trace=True):
            v = harness.read_metric(bench_dir, m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = _end_to_end(bench, args.workload, lat, n_obs, win, setup_s)

    drv.release()
    gc.collect()
    numbers = drv.check(control=bool(args.control))
    limits = cfg["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = bool(lat) and all(numbers[k] <= limits[k] for k in limits)
    who = "bfloat16 control" if args.control else "program"
    for k, v in numbers.items():
        lim = f" limit {limits[k]!r}" if k in limits else ""
        print(f"chipbench: {k} {v!r}{lim}", file=sys.stderr)
    print(f"chipbench: correct {correct} ({who})", file=sys.stderr,
          flush=True)
    print(harness.result_line(correct, n_obs, 0, metrics, device, checks,
                              breakdown), flush=True)
    return 0


def _end_to_end(bench, cell, lat, n_obs, win, setup_s) -> dict:
    values = {}
    if lat:
        ms = sorted(1e3 * x for x in lat)
        values["tick_ms_p50"] = statistics.median(ms)
        values["tick_ms_p95"] = statistics.quantiles(ms, n=20)[-1]
    values["obs_per_s"] = n_obs / win.length
    values["setup_s"] = setup_s
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in harness.metrics_for(bench, cell, trace=False)
            if m["name"] in values}


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _clear(path: Path) -> None:
    import shutil
    if path.exists():
        shutil.rmtree(path)


if __name__ == "__main__":
    sys.exit(main())
