"""The benchmark harness: one cell, one ``Wilkins.run``, one result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds every piece by that name and needs no edit for a new one:

* ``configs/<config>.json`` -- the deployment's sizes, source, ``reduced``
  and ``assumed`` lists, and ``module``: the file ``configs/<module>.py``
  that holds its workflow YAML, its task functions and its plain reference;
* ``traffic/<traffic>.json`` -- the flow-control setting (``io_freq``) and
  the producer steps taken as warm-up (``warmup_steps``);
* ``metrics/<metric>.py`` -- one reader per metric, ``read(reading)``,
  returning a number or None when it finds nothing to read.

Run shape: the producer task asks ``Run.keep_going(step)`` before each
step.  The first ``warmup_steps`` steps, and any further ones until every
consumer instance has finished one analysis, are set-up; the window then
runs for ``seconds``, and the producer stops at the first step boundary
after it.  The consumers drain.  Only then is the plain reference run and
each delivery compared with it.

A traced run measures its window as an untraced run does, with the
profiler off: every host-clock and counter reading comes from there.  At
the window's close the producer starts the profiler and goes on for
``TRACE_SECONDS`` of steps, the profiled tail, from which the device
readings and the breakdown come; the profiler stops on the harness's own
thread once the run is over.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: a traced run profiles this many seconds of steps after its window
TRACE_SECONDS = 5.0


# ------------------------------------------------------------------ pieces
def load_benchmark(path: str = BENCHMARK) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"({[c['name'] for c in bench['workloads']]})")


def load_json(bench_dir: str, kind: str, name: str) -> Dict[str, Any]:
    with open(os.path.join(bench_dir, kind, name + ".json")) as f:
        return json.load(f)


_MODULES: Dict[str, Any] = {}


def load_module(bench_dir: str, kind: str, name: str):
    """Import ``<bench_dir>/<kind>/<name>.py`` under a name of its own, once
    per process, so that runs in one process share its compiled programs."""
    path = os.path.abspath(os.path.join(bench_dir, kind, name + ".py"))
    if path in _MODULES:
        return _MODULES[path]
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def metrics_for(bench: Dict[str, Any], cell: str, trace: bool
                ) -> List[Dict[str, Any]]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer metrics
    (``trace`` on): every entry that lists the cell, or lists none."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------------ the run
@dataclass
class Span:
    role: str      # producer: step, write; consumer: open, h2d, analyse
    task: str
    instance: int
    step: Optional[int]
    t0: float
    t1: float


class Compiles:
    """Counts XLA backend compiles (JAX's own monitoring events)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1


@dataclass
class Run:
    """What one run shares between the task functions and the harness.

    Task functions call ``keep_going`` (producer, before each step),
    ``span`` (around each call into a layer), ``closing`` (producer, just
    before its file closes) and ``deliver`` (consumer, once its analysis
    result is on the host).  Everything is stamped with
    ``time.monotonic()``, the clock ``repro.obs`` spans use too."""

    seed: int
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    seconds: float
    trace_dir: Optional[str] = None
    compiles: Optional[Compiles] = None
    lock: threading.Lock = field(default_factory=threading.Lock)
    spans: List[Span] = field(default_factory=list)
    close_enter: Dict[int, float] = field(default_factory=dict)
    close_done: Dict[int, float] = field(default_factory=dict)
    deliveries: List[Dict[str, Any]] = field(default_factory=list)
    warmed: set = field(default_factory=set)
    t_start: Optional[float] = None
    t_stop: Optional[float] = None
    step0: Optional[int] = None      # the first step of the window
    step1: Optional[int] = None      # the first step after it
    stats0: Dict[str, Any] = field(default_factory=dict)
    stats1: Dict[str, Any] = field(default_factory=dict)
    compiles0: int = 0
    compiles1: int = 0
    obs: Any = None          # the repro.obs SpanRecorder of a traced run
    profiling: bool = False  # the profiler was started and not yet stopped
    t_trace: Optional[float] = None
    _traced: Any = None      # the open ``bench/traced`` annotation

    @property
    def consumers(self) -> int:
        return int(self.cfg["consumer_instances"])

    def key(self):
        """The run's PRNG key from ``seed``, whatever its size: JAX keys
        hold 32 bits, so the high word is folded in."""
        import jax

        return jax.random.fold_in(jax.random.key(self.seed & 0xFFFFFFFF),
                                  (self.seed >> 32) & 0xFFFFFFFF)

    @contextlib.contextmanager
    def span(self, role: str, task: str, instance: int,
             step: Optional[int] = None):
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(f"bench/{task}.{role}"):
            yield
        t1 = time.monotonic()
        with self.lock:
            self.spans.append(Span(role, task, instance, step, t0, t1))

    def keep_going(self, step: int) -> bool:
        """Producer, before step ``step``: False once the window, and in a
        traced run the profiled tail after it, is over."""
        now = time.monotonic()
        if self.t_start is None:
            if step >= int(self.traffic["warmup_steps"]):
                with self.lock:
                    ready = len(self.warmed) >= self.consumers
                if ready:
                    self._open_window(step)
            return True
        if self.t_stop is None:
            if now < self.t_start + self.seconds:
                return True
            self._close_window(step)
            if self.trace_dir is None:
                return False
            self._start_trace()
            return True
        if now < self.t_trace + TRACE_SECONDS:
            return True
        self._traced.__exit__(None, None, None)   # the tail ends here
        self._traced = None
        return False

    def _open_window(self, step: int) -> None:
        from repro.core.datamodel import transport_stats

        self.stats0 = transport_stats().snapshot()
        self.compiles0 = self.compiles.n if self.compiles else 0
        self.step0 = step
        self.t_start = time.monotonic()

    def _close_window(self, step: int) -> None:
        from repro.core.datamodel import transport_stats

        self.t_stop = time.monotonic()
        self.step1 = step
        self.compiles1 = self.compiles.n if self.compiles else 0
        self.stats1 = transport_stats().snapshot()

    def _start_trace(self) -> None:
        """Profile the steps after the window: a few seconds, since a whole
        window of host transfers would hold tens of GiB of trace events."""
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1   # annotations, not the runtime's own
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.profiling = True
        self._traced = jax.profiler.TraceAnnotation("bench/traced")
        self._traced.__enter__()
        self.t_trace = time.monotonic()

    def stop_profiler(self) -> None:
        """The harness's thread, once the tasks are done: write the trace."""
        import jax

        if self.profiling:
            self.profiling = False
            jax.profiler.stop_trace()

    def closing(self, step: int) -> None:
        """Producer: the file of ``step`` is about to close."""
        self.close_enter[step] = time.monotonic()

    def closed(self, step: int) -> None:
        self.close_done[step] = time.monotonic()

    def deliver(self, instance: int, step: int, result: Tuple,
                box: Optional[Tuple] = None) -> None:
        """Consumer: the analysis of ``step`` is ready on the host."""
        now = time.monotonic()
        with self.lock:
            self.deliveries.append({"instance": instance, "step": int(step),
                                    "result": [int(v) for v in result],
                                    "box": box, "t": now})
            self.warmed.add(instance)


# ------------------------------------------------------------ the reading
@dataclass
class Reading:
    """What a metric reader sees once the run is over."""

    run: Run
    setup_s: float
    every: bool                      # an `all` edge: steps count once analysed
    trace: Optional[Dict[str, Any]]  # trace_reduce's result, traced runs only

    @property
    def t_end(self) -> float:
        return self.run.t_start + self.run.seconds

    @property
    def stats1(self) -> Dict[str, Any]:
        return self.run.stats1

    def in_window(self, step: Optional[int]) -> bool:
        return step is not None and self.run.step0 <= step < self.run.step1

    def window_steps(self) -> List[int]:
        """Producer steps begun in the window."""
        return sorted(s for s in self.run.close_done if self.in_window(s))

    def completion(self) -> Dict[int, float]:
        """When each window step was done: its close, or on an `all` edge
        the last of its analyses."""
        r = self.run
        if not self.every:
            return {s: r.close_done[s] for s in self.window_steps()}
        seen: Dict[int, List[float]] = {}
        for d in r.deliveries:
            seen.setdefault(d["step"], []).append(d["t"])
        return {s: max(ts) for s, ts in seen.items()
                if self.in_window(s) and len(ts) >= r.consumers}

    def window_deliveries(self) -> List[Dict[str, Any]]:
        return [d for d in self.run.deliveries if self.in_window(d["step"])]

    def spans(self, role: str) -> List[Span]:
        return [s for s in self.run.spans
                if s.role == role and self.in_window(s.step)]


# ------------------------------------------------------------ correctness
def served(io_freq: int, step: int) -> bool:
    """Which producer steps an edge delivers (``Channel.offer``'s rule,
    written out): `all` every one, `some` every ``io_freq``-th close."""
    if io_freq in (0, 1):
        return True
    if io_freq > 1:
        return (step + 1) % io_freq == 0
    raise ValueError(f"io_freq {io_freq}: no fixed delivery set to compare")


def compare(run: Run, io_freq: int, reference: Callable) -> Dict[str, Any]:
    """Every delivery against the plain reference, and the delivered set
    against the edge's flow control.  Each number has the limit 0."""
    produced = sorted(run.close_done)
    want = {(i, s) for s in produced if served(io_freq, s)
            for i in range(run.consumers)}
    got: Dict[Tuple[int, int], List[Dict[str, Any]]] = {}
    for d in run.deliveries:
        got.setdefault((d["instance"], d["step"]), []).append(d)
    expected = reference(run, sorted(want | set(got)))
    mismatched = wrong_box = 0
    for k, ds in got.items():
        ref = expected[k]
        for d in ds:
            mismatched += d["result"] != ref["result"]
            if d["box"] is not None or ref.get("box") is not None:
                wrong_box += _box(d["box"]) != _box(ref.get("box"))
    duplicated = sum(len(ds) - 1 for ds in got.values())
    checks = {
        "mismatched": mismatched,
        "wrong_box": wrong_box,
        "missing": len(want - set(got)),
        "unexpected": len(set(got) - want),
        "duplicated": duplicated,
    }
    return {"attempted": len(want | set(got)),
            "failed": mismatched + len(want - set(got)) + len(set(got) - want)
            + duplicated + wrong_box,
            "checks": {k: {"value": v, "limit": 0} for k, v in checks.items()}}


def _box(b):
    return None if b is None else [list(map(int, x)) for x in b]


# ------------------------------------------------------------ one cell
def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compile cache at a fixed place in the checkout
    (``<root>/.jax_cache``), or where ``JAX_COMPILATION_CACHE_DIR`` says.
    Every program is cached, however quick its compile, so that a second
    run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> Dict[str, Any]:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             devices, t_proc: float, bench_dir: str = BENCH_DIR,
             benchmark: str = BENCHMARK,
             device_lines: Optional[List[Tuple[str, str]]] = None,
             log: Callable[[str], None] = lambda s: None) -> Dict[str, Any]:
    """Run one cell through ``Wilkins.run`` and return its result line.

    ``devices`` are the cell's devices; ``t_proc`` is the monotonic time the
    process started, from which ``setup_s`` counts; ``device_lines`` picks
    the trace's device op lines (the TPU's by default)."""
    from repro.core import Wilkins

    from bench import trace_reduce

    bench = load_benchmark(benchmark)
    cell = find_cell(bench, cell_name)
    cfg = load_json(bench_dir, "configs", cell["config"])
    traffic = load_json(bench_dir, "traffic", cell["traffic"])
    module = load_module(bench_dir, "configs", cfg["module"])
    io_freq = int(traffic["io_freq"])

    scratch = tempfile.mkdtemp(prefix="bench_")
    run = Run(seed=seed, cfg=cfg, traffic=traffic, seconds=seconds,
              trace_dir=os.path.join(scratch, "trace") if trace else None,
              compiles=Compiles())
    try:
        funcs = {name: _noting_comm(run, fn)
                 for name, fn in module.tasks(run).items()}
        w = Wilkins(module.workflow(cfg, io_freq), funcs,
                    devices=devices, spill_dir=os.path.join(scratch, "spill"))
        try:
            report = w.run(timeout=seconds + 600, trace=True if trace else None)
            failures = [str(f) for f in report.failures]
            del report
        except Exception as e:  # a task failed or hung: the run is not correct
            failures = [f"{type(e).__name__}: {e}"]
        t0 = time.monotonic()
        run.stop_profiler()
        if trace:
            log(f"profiler stopped in {time.monotonic() - t0:.3f} s")
        info = device_info(devices)
        del w
        gc.collect()
        reduced = None
        if trace and run.t_trace is not None:
            t0 = time.monotonic()
            reduced = trace_reduce.reduce_trace(
                run.trace_dir, device_lines if device_lines is not None
                else trace_reduce.tpu_lines(d.id for d in devices))
            log(f"trace reduced in {time.monotonic() - t0:.3f} s")
    finally:
        run.stop_profiler()
        shutil.rmtree(scratch, ignore_errors=True)

    if run.t_start is None or run.t_stop is None:
        failures.append("the window never opened or never closed")
    log(f"compiles in window: {run.compiles1 - run.compiles0}")
    if failures:
        checks = {"task_failures": {"value": len(failures), "limit": 0}}
        for f in failures:
            log(f"failure: {f}")
        return {"correct": False, "attempted": len(run.deliveries),
                "failed": len(failures), "metrics": {}, "device": info,
                "checks": checks}

    reading = Reading(run=run, setup_s=run.t_start - t_proc,
                      every=io_freq in (0, 1), trace=reduced)
    log(f"window: steps {run.step0}..{run.step1 - 1}, "
        f"{len(reading.window_deliveries())} deliveries")
    metrics = {}
    for m in metrics_for(bench, cell_name, trace):
        value = load_module(bench_dir, "metrics", m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace and reduced is not None:
        info["busy_s"] = reduced["busy_s"]
        info["window_s"] = reduced["window_s"]

    t0 = time.monotonic()
    verdict = compare(run, io_freq, module.reference)
    log(f"reference compared in {time.monotonic() - t0:.3f} s")
    out = {"correct": verdict["failed"] == 0,
           "attempted": verdict["attempted"], "failed": verdict["failed"],
           "metrics": metrics, "device": info}
    if trace and reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = verdict["checks"]
    return out


def _noting_comm(run: Run, fn: Callable) -> Callable:
    """Wrap a task so the harness sees the run's ``repro.obs`` recorder."""
    def task(comm):
        if comm.tracer is not None:
            run.obs = comm.tracer
        return fn(comm)
    return task


def print_checks(checks: Dict[str, Dict[str, Any]], stream=sys.stderr) -> None:
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=stream,
              flush=True)
