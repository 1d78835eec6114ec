"""One run of one cell: set-up, the measured window, the check, the
result line.

A *system* (perfbench/systems/<kind>.py, named by the configuration's
``system`` key) stands a deployment up and drives it; this module owns
the clock, the profiler, the counters' snapshots, the metric readers and
what is printed.  A system gives:

  SPANS                      the names of the profiler spans it opens
  TRAFFIC_KIND               the ``kind`` of traffic file it can drive
  System(cell, seed, span)   nothing heavy yet
  .setup()                   stand up, load or compile, warm
  .run_window(seconds, on_open, on_close)
                             drive; ``on_open()`` ends set-up and starts
                             the clock, ``on_close()`` stops it; whatever
                             it does before and after them is untimed
  .counters()                a flat dict of the program's counters now
  .log                       what the window saw, for the readers
  .verify()                  [Check, ...] against the plain reference
  .slice_gate()              optional: called as the traced slice opens,
                             gives a function that says whether the
                             slice holds what the trace readers need
  .attempted, .failed        operations, all phases
  .close()                   stop everything, remove what was written
"""

from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from typing import NamedTuple

from . import device as device_mod
from . import manifest as manifest_mod
from . import trace as trace_mod


class Check(NamedTuple):
    """One number compared, beside its limit: correct while
    ``value <= limit``."""
    name: str
    value: float
    limit: float


@dataclass
class Reading:
    """What a metric reader may look at."""
    cell: manifest_mod.Cell
    device: dict
    peaks: dict
    seconds: float
    setup_s: float
    log: object
    before: dict
    after: dict
    compiles_in_window: int
    memory_peak_bytes: int
    trace: trace_mod.TraceSummary | None = None
    #: the traced slice of the window, on the clock of `log`
    slice_t: tuple[float, float] | None = None

    def delta(self, key: str) -> float:
        """Counter `key`, close of the window minus its opening."""
        return self.after[key] - self.before[key]


class SliceTracer(threading.Thread):
    """Traces `seconds` of the window, starting `offset` after it
    opens.  A slice and not the window: a trace of a minute of twelve
    OSDs is too large to read back inside a run's time."""

    def __init__(self, trace_dir: str, offset: float, seconds: float,
                 max_seconds: float = 0.0, make_gate=None):
        super().__init__(name="perfbench-tracer", daemon=True)
        self.trace_dir = trace_dir
        self.offset = offset
        self.seconds = seconds
        #: ``make_gate()`` is called as the slice opens and gives a
        #: function that says whether the slice holds what its readers
        #: need; until it does the slice goes on, to `max_seconds`
        self.max_seconds = max(max_seconds, seconds)
        self.make_gate = make_gate
        self.t: tuple[float, float] | None = None
        self.stop_s = 0.0
        self.error: BaseException | None = None
        self._cancel = threading.Event()

    def run(self) -> None:
        import jax
        try:
            if self._cancel.wait(self.offset):
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            try:
                t_a = time.perf_counter()
                gate = self.make_gate() if self.make_gate else None
                with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                    self._cancel.wait(self.seconds)
                    while (gate is not None and not gate()
                           and time.perf_counter() - t_a < self.max_seconds
                           and not self._cancel.wait(0.02)):
                        pass
                self.t = (t_a, time.perf_counter())
            finally:
                t_stop = time.perf_counter()
                jax.profiler.stop_trace()
                self.stop_s = time.perf_counter() - t_stop
        except BaseException as e:      # read by the main thread
            self.error = e

    def finish(self) -> None:
        self._cancel.set()
        self.join(timeout=200.0)
        if self.is_alive():
            raise RuntimeError("the profiler did not stop")
        if self.error is not None:
            raise self.error


def profiler_span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, out=sys.stdout, err=sys.stderr,
             parked: str = "") -> int:
    """The whole of one run of a cell of BENCHMARK.json (or, for
    perfbench/control.py, of the entries parked under `parked`).
    Returns the exit code."""
    manifest = manifest_mod.load_manifest()
    if parked:
        manifest = manifest_mod.with_parked(manifest, parked)
    cell = manifest_mod.load_cell(manifest, workload)
    section = "per_layer" if trace else "end_to_end"
    wanted = manifest_mod.metrics_for(manifest, workload, section)
    return run_loaded(cell, wanted, seed, seconds, trace, t_start, out, err)


def run_loaded(cell: manifest_mod.Cell, wanted: list[dict], seed: int,
               seconds: float, trace: bool, t_start: float,
               out=sys.stdout, err=sys.stderr) -> int:
    """One run of `cell`, reporting the metrics `wanted` (manifest
    entries)."""
    workload = cell.name
    import jax
    import ceph_tpu  # noqa: F401  (x64 on before any array exists)
    from ceph_tpu.common.compile_cache import place_compile_cache
    cache_dir = place_compile_cache()
    # keep every program, however quick it was to compile, so that a
    # second run's set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_mod.device_or_exit(cell.chips)
    peaks = device_mod.peaks_of(device["kind"])
    watch = device_mod.CompileWatch()
    note(err, "start", workload=workload, seed=seed, seconds=seconds,
         trace=int(trace), device=device, compile_cache=cache_dir)

    system_mod = manifest_mod.load_system(cell.config["system"])
    if cell.traffic["kind"] != system_mod.TRAFFIC_KIND:
        raise SystemExit(
            f"perfbench: {cell.config['system']} drives traffic of kind "
            f"{system_mod.TRAFFIC_KIND!r}, {cell.traffic_name} is "
            f"{cell.traffic['kind']!r}")
    system = system_mod.System(cell, seed,
                               profiler_span if trace else None)
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else ""
    tracer = None
    marks: dict = {}

    def on_open() -> None:
        nonlocal tracer
        gc.collect()
        gc.freeze()
        marks["compiles0"] = watch.facts()
        marks["env0"] = environment_counts()
        marks["before"] = system.counters()
        if trace:
            tracer = SliceTracer(
                trace_dir, float(cell.traffic.get("trace_offset_s", 1.0)),
                float(cell.traffic.get("trace_seconds", 3.0)),
                float(cell.traffic.get("trace_max_seconds", 0.0)),
                getattr(system, "slice_gate", None))
            tracer.start()
        marks["setup_s"] = time.perf_counter() - t_start

    def on_close() -> None:
        marks["after"] = system.counters()
        marks["env1"] = environment_counts()
        marks["compiles1"] = watch.facts()

    try:
        system.setup()
        note(err, "set up", wall_s=round(time.perf_counter() - t_start, 3),
             jax=watch.facts())
        system.run_window(seconds, on_open, on_close)
        if tracer is not None:
            tracer.finish()
        memory_peak = device_mod.memory_peak_bytes()
        note(err, "window closed", setup_s=round(marks["setup_s"], 3),
             host={k: round(marks["env1"][k] - marks["env0"][k], 3)
                   for k in marks["env0"]},
             **system.notes(marks["before"], marks["after"]))
        t0 = time.perf_counter()
        checks = system.verify()
        note(err, "verified", wall_s=round(time.perf_counter() - t0, 3))
        attempted, failed = system.attempted, system.failed
    except BaseException:
        shutil.rmtree(trace_dir, ignore_errors=True)
        raise
    finally:
        system.close()
        if tracer is not None and tracer.is_alive():
            tracer.finish()
        gc.unfreeze()

    reading = Reading(
        cell=cell, device=device, peaks=peaks, seconds=seconds,
        setup_s=marks["setup_s"], log=system.log, before=marks["before"],
        after=marks["after"],
        compiles_in_window=(marks["compiles1"]["compiles"]
                            - marks["compiles0"]["compiles"]),
        memory_peak_bytes=memory_peak)
    device_out = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    if trace:
        try:
            t0 = time.perf_counter()
            raw = trace_mod.read_xplane(trace_mod.find_xplane(trace_dir),
                                        system_mod.SPANS)
            reading.trace = trace_mod.summarize(raw)
            note(err, "trace read", wall_s=round(time.perf_counter() - t0, 3),
                 profiler_stop_s=round(tracer.stop_s, 3),
                 slice_s=round(reading.trace.window_s, 3),
                 program_calls=reading.trace.program_calls)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        reading.slice_t = tracer.t
        device_out["busy_s"] = reading.trace.busy_s
        device_out["window_s"] = reading.trace.window_s
        breakdown = {"device_ops": reading.trace.device_ops,
                     "idle_gaps": reading.trace.idle_gaps}

    metrics = {}
    for m in wanted:
        value = manifest_mod.load_reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    checks = list(checks) + [Check("failed_ops", failed, 0)]
    correct = all(c.value <= c.limit for c in checks)
    compared = {c.name: {"value": c.value, "limit": c.limit}
                for c in checks}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics,
              "device": device_out}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for c in checks:
        print(f"compared {c.name}: value {c.value} limit {c.limit} "
              f"{'ok' if c.value <= c.limit else 'NOT CORRECT'}",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0


def note(err, tag: str, **facts) -> None:
    """A line for the builder, on standard error."""
    print(tag, json.dumps(facts, sort_keys=True, default=str),
          file=err, flush=True)


def environment_counts() -> dict:
    """What the process has used so far; the difference over the
    window is printed beside the window's own facts."""
    import resource
    ru = resource.getrusage(resource.RUSAGE_SELF)
    counts = {"cpu_s": ru.ru_utime + ru.ru_stime,
              "gc_collections": sum(s["collections"]
                                    for s in gc.get_stats())}
    try:
        with open("/proc/self/io") as f:
            io = dict(line.split(": ") for line in f.read().splitlines())
        counts["write_bytes"] = int(io["write_bytes"])
    except OSError:
        pass
    return counts

