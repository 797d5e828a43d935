"""The benchmark harness: one run of one cell of ``BENCHMARK.json``.

Everything is found by name.  A cell names a configuration and a traffic
mix; the configuration's file (``configs`` entry of ``BENCHMARK.json``)
holds its sizes, the mix's file ``bench/traffic/<traffic>.json`` holds its
parameters and names its driver ``bench/drivers/<driver>.py``, and each
per-layer metric is read by ``bench/metrics/<metric>.py``.  Adding a
configuration, a mix or a metric adds files and entries; nothing here
changes.

A run: refuse unless JAX sees the accelerator and the cell's chips; set
up (make inputs and weights from ``--seed``, warm every shape the window
uses); measure whole units of work (a query, a step, a ``generate`` call)
until ``--seconds`` have passed and the unit in flight has finished;
read device memory; free the program's state; compare what the window
produced with the plain reference; print earlier lines, the compared
numbers on standard error and one JSON result as the last line of
standard output.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: unit annotation of the traced window
WINDOW_ANNOTATION = "bench.window"
#: JAX monitoring events that are plan time: tracing, lowering, and the
#: backend compile (which includes a persistent-cache load)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Refused(Exception):
    """The run cannot measure here: no result is printed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def process_start_s() -> float:
    """``time.time()`` at which this process started (from /proc), or the
    harness's import time where /proc is absent."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        hz = os.sysconf("SC_CLK_TCK")
        return time.time() - uptime + start_ticks / hz
    except (OSError, ValueError, IndexError):
        return _IMPORTED_AT


_IMPORTED_AT = time.time()


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str = None):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = name or "bench_" + os.path.relpath(path, BENCH).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# resolving a cell
# ---------------------------------------------------------------------------
class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration and mix."""

    def __init__(self, bench: dict, name: str, root: str = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise Refused(f"no workload {name!r} in BENCHMARK.json; have "
                          f"{sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH, "traffic", self.workload["traffic"] + ".json"))
        self.chips = int(self.workload["chips"])
        self.driver_path = os.path.join(BENCH, "drivers",
                                        self.traffic["driver"] + ".py")
        # end-to-end metrics this cell reports, and per-layer ones
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m["workloads"]
                          or ("workloads" not in m and m["moves"] in reported)]

    def reference(self):
        """The configuration's plain reference, beside its file."""
        path = os.path.join(ROOT, self.config_entry["file"])
        return load_module(path[:-len(".json")] + "_ref.py")

    def driver(self):
        return load_module(self.driver_path)


# ---------------------------------------------------------------------------
# compile clock (JAX monitoring events)
# ---------------------------------------------------------------------------
class CompileClock:
    """Spans of JAX's trace, lower and backend-compile events (the last
    includes persistent-cache loads), persistent-cache hits and misses."""

    def __init__(self):
        import jax

        self.spans = []          # (event, start, end) on time.time()
        self.cache_hits = 0
        self.cache_misses = 0

        def on_span(event, start, end, **_):
            if event in COMPILE_EVENTS:
                self.spans.append((event, start, end))

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        jax.monitoring.register_event_time_span_listener(on_span)
        jax.monitoring.register_event_listener(on_event)

    def seconds(self, event: str, t0: float = 0.0, t1: float = float("inf")
                ) -> float:
        return sum(e - s for ev, s, e in self.spans
                   if ev == event and s >= t0 and e <= t1)

    def backend_compiles(self, t0: float, t1: float) -> int:
        return sum(1 for ev, s, e in self.spans
                   if ev == COMPILE_EVENTS[2] and s >= t0 and e <= t1)


def union_seconds(intervals, t0: float, t1: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[t0, t1]``."""
    from tracereduce import merge_intervals

    clipped = ((max(s, t0), min(e, t1)) for s, e in intervals)
    return sum(e - s for s, e in merge_intervals((s, e) for s, e in clipped
                                                 if e > s))


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------
class Run:
    """What per-layer readers read: the window, the work, the trace
    reduction, the compile spans and the program's spans."""

    def __init__(self, cell: Cell, seed: int, seconds: float):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.chips = cell.chips
        self.units = 0           # whole units of work in the window
        self.unit_s = []         # seconds of each unit, in order
        self.collections = []    # (generation, seconds) of GC in the window
        self.work = 0            # rows, tokens ... the end-to-end metric counts
        self.window = None       # (t0, t1) on time.perf_counter()
        self.window_wall = None  # (t0, t1) on time.time()
        self.end_to_end = {}
        self.trace = None        # bench.trace.reduce() of the traced window
        self.clock = None
        self.spans = []          # program spans (name, t0, t1), perf_counter
        self.peak = None
        self.device_kind = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def share_of_window(self, intervals, wall: bool = False) -> float:
        """Percent of the window covered by ``(start, end)`` intervals on
        ``time.perf_counter`` (or ``time.time`` with ``wall``)."""
        t0, t1 = self.window_wall if wall else self.window
        return 100.0 * union_seconds(intervals, t0, t1) / (t1 - t0)

    def idle_share(self):
        """Percent of the traced window in which no device operation ran."""
        if self.trace is None or self.trace["devices"] == 0:
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])


def _memory_peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def measure(run: Run, impl, trace: bool, trace_dir: str = None) -> None:
    """Drive whole units until ``run.seconds`` have passed; the unit in
    flight finishes.  With ``trace`` the profiler records the window."""
    import jax

    if trace:
        jax.profiler.start_trace(trace_dir)
    units = work = 0
    failed = 0
    unit_s = []
    collections = []            # (generation, seconds) of Python's GC
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            collections.append((info["generation"],
                                time.perf_counter() - gc_start[0]))

    gc.callbacks.append(on_gc)
    wall0 = time.time()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
        while True:
            try:
                u0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench." + impl.unit_name):
                    work += impl.unit()
                unit_s.append(time.perf_counter() - u0)
                units += 1
            except Exception:  # a unit that fails counts, the run goes on
                traceback.print_exc()
                failed += 1
                if failed >= 3:
                    break
            if time.perf_counter() - t0 >= run.seconds:
                break
    t1 = time.perf_counter()
    wall1 = time.time()
    gc.callbacks.remove(on_gc)
    if trace:
        jax.profiler.stop_trace()
    run.units, run.work, run.failed = units, work, failed
    run.unit_s = unit_s
    run.collections = collections
    run.window, run.window_wall = (t0, t1), (wall0, wall1)


def find_xplane(trace_dir: str):
    for dirpath, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    return None


def parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json on the accelerator.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare_jax(chips: int):
    """Compile cache in the checkout; refuse without the accelerator."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devices


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            devices, started: float):
    """Set up, measure, check; returns the result line as a dict."""
    import tempfile

    import jax

    from peaks import peaks

    run = Run(cell, seed, seconds)
    run.device_kind = devices[0].device_kind
    run.peak = peaks(run.device_kind)
    run.clock = CompileClock()
    from repro.kernels import dispatch

    dispatch.reset_counts()
    impl = cell.driver().Driver(cell.config, cell.traffic, seed,
                                cell.reference(), cell.name)
    impl.setup()
    warm_hits, warm_misses = run.clock.cache_hits, run.clock.cache_misses
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        setup_end = time.time()
        measure(run, impl, trace, tdir)
        if trace:
            from tracereduce import reduce as reduce_trace

            path = find_xplane(tdir)
            run.trace = reduce_trace(path, WINDOW_ANNOTATION) if path else None
    run.spans = impl.spans()
    run.end_to_end = {cell.traffic["metric"]: run.work / run.window_s,
                      "setup_s": setup_end - started}
    memory_peak = _memory_peak_bytes()
    w0, w1 = run.window_wall
    log(f"compile: cache_dir={os.environ.get('JAX_COMPILATION_CACHE_DIR')} "
        f"setup_cache_hits={warm_hits} setup_cache_misses={warm_misses} "
        f"setup_backend_compile_s="
        f"{run.clock.seconds(COMPILE_EVENTS[2], 0, w0)} "
        f"window_backend_compiles={run.clock.backend_compiles(w0, w1)} "
        f"window_cache_misses={run.clock.cache_misses - warm_misses} "
        f"window_backend_compile_s={run.clock.seconds(COMPILE_EVENTS[2], w0, w1)}")
    log(f"dispatch counts (trace-time decisions): "
        f"{json.dumps(dispatch.counts())}")
    for line in impl.notes():
        log(line)
    log(f"window: units={run.units} work={run.work} seconds={run.window_s} "
        f"failed={run.failed} unit_s={[round(u, 4) for u in run.unit_s]}")
    full = [t for g, t in run.collections if g == 2]
    log(f"gc in window: collections={len(run.collections)} "
        f"seconds={sum(t for _, t in run.collections)} "
        f"full={len(full)} longest_full_s={max(full, default=0.0)}")
    impl.release()
    jax.clear_caches()
    checks = impl.check()
    metrics = {}
    if trace:
        for m in cell.per_layer:
            reader = load_module(os.path.join(BENCH, "metrics",
                                              m["name"] + ".py"))
            value = reader.read(run)  # a reader that fails fails the run
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": run.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": cell.chips, "memory_peak_bytes": memory_peak}
    result = {"correct": None, "attempted": run.units + run.failed,
              "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace["top_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    passed = all(c["value"] is not None and c["value"] <= c["limit"]
                 for c in checks.values())
    result["correct"] = bool(passed and run.failed == 0 and run.units > 0)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    started = process_start_s()
    args = parse_args(argv)
    sys.path.insert(0, BENCH)
    try:
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise Refused(f"no program (src/repro) beside {BENCH}")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell = Cell(bench, args.workload)
        devices = prepare_jax(cell.chips)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices, started)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
