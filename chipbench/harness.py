"""What every cell shares: the benchmark file, the span recorder, the
measured window, the compile counter, the device gate and the result line.

Cells are found by name: a workload in ``BENCHMARK.json`` names a
configuration (its ``file``, ``configs/<name>.json``, whose ``runner`` key
picks the module under ``runners/``) and a traffic mix
(``traffic/<name>.json``); each per-layer metric is read by
``metrics/<name>.py``.  So a new configuration, traffic mix or metric
enters as files and entries alone.  A configuration file may carry
``test_sizes``, the keys the benchmark's own tests change to run it on the
CPU; nothing here reads it.  A closed-loop traffic file may carry a
``faults`` block, which ``runners/executor.py`` describes.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import time
from contextlib import contextmanager
from pathlib import Path

HERE = Path(__file__).resolve().parent


class BenchError(Exception):
    """The benchmark cannot run: no chip, an unknown name, a bad file."""


# ---- the benchmark's files -------------------------------------------------
def load_json(path: Path):
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def find_cell(root: Path, name: str):
    """(benchmark, workload entry, configuration entry) for a cell name."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return bench, cell, configs[cell["config"]]


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end metrics without a trace,
    its per-layer metrics with one."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_module(path: Path):
    """Import a file by path (metric readers and runners have dotted or
    otherwise non-package names)."""
    if not path.is_file():
        raise BenchError(f"missing module {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(bench_dir: Path, name: str, ctx):
    """The value of per-layer metric ``name`` from ``ctx``, or None when
    its reader (``metrics/<name>.py``) finds nothing to read."""
    return load_module(bench_dir / "metrics" / f"{name}.py").read(ctx)


def peaks_for(device_kind: str) -> dict:
    """The published peaks of a device kind (``peaks.json``); an unknown
    kind is an error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r}; "
                         f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


# ---- the device ------------------------------------------------------------
def device_gate(chips: int):
    """The TPU devices, or BenchError when JAX finds no TPU or fewer chips
    than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found: JAX sees {len(devs)} "
                         f"{devs[0].platform} device(s)")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> str:
    """JAX's persistent compile cache in the checkout (the program's own
    helper), holding every program, however quick its compile."""
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


@contextmanager
def count_compiles():
    """Count the programs XLA prepares inside the block, ``box[0]`` (JAX
    reports a backend compile also when the persistent cache serves the
    program), and how many of them the cache served, ``box[1]``."""
    import jax
    box = [0, 0]

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            box[0] += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            box[1] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield box
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


@contextmanager
def gc_passes():
    """Durations (s) of the collector's full passes (generation 2) inside
    the block, in ``box``."""
    box: list[float] = []
    start = [0.0]

    def callback(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            box.append(time.perf_counter() - start[0])

    gc.callbacks.append(callback)
    try:
        yield box
    finally:
        gc.callbacks.remove(callback)


# ---- spans and the window --------------------------------------------------
class WindowClosed(Exception):
    """Raised from a span to stop the program's run once the window has
    closed."""


class Window:
    """The measured interval: opens, closes ``seconds`` later, and ends
    when the last counted work ends."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.t_open = self.t_close = self.t_end = None

    def open(self) -> None:
        self.t_open = time.perf_counter()
        self.t_close = self.t_open + self.seconds

    def closed(self, now: float | None = None) -> bool:
        return (time.perf_counter() if now is None else now) >= self.t_close

    def end(self, now: float | None = None) -> None:
        self.t_end = time.perf_counter() if now is None else now

    @property
    def length(self) -> float:
        return self.t_end - self.t_open


class _Span:
    __slots__ = ("rec", "phase", "data", "t0", "ann")

    def __init__(self, rec, phase, data):
        self.rec, self.phase, self.data = rec, phase, data

    def __enter__(self):
        self.t0 = time.perf_counter()
        if self.rec.hook is not None:
            self.rec.hook(self.phase, self.t0, self.data)
        self.ann = None
        if self.rec.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.phase)
            self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.rec.spans.append((self.phase, self.t0, time.perf_counter(),
                               self.rec.run, self.data.get("n", 0)))
        return False


class Recorder:
    """A ``repro.obs`` tracer that keeps only spans: ``enabled`` is False,
    so the program builds no event payloads, but every span it enters
    (``plan``, ``tick_step``) is timed, tagged with the current ``run``,
    and, with ``annotate``, written into the profiler's trace on the
    device's clock.  ``hook(phase, t0, data)`` runs as a span opens and
    may raise ``WindowClosed``."""

    enabled = False

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: list[tuple] = []
        self.run = 0
        self.hook = None

    def emit(self, kind, t_sim=0.0, **data) -> None:
        pass

    def span(self, phase, t_sim=0.0, **data):
        return _Span(self, phase, data)

    def of(self, phase: str, t_open: float, t_close: float) -> list[tuple]:
        """Spans of ``phase`` that started inside [t_open, t_close)."""
        return [s for s in self.spans
                if s[0] == phase and t_open <= s[1] < t_close]


# ---- the result ------------------------------------------------------------
def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
