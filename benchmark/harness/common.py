"""Pieces every driver shares: files found by name, the device, the
compile counter, the comparison that decides ``correct``, the traced
window."""

import glob
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
#: traces and scratch files of runs; git-ignored. Each run keeps its
#: own under ``run_<pid>`` and removes that when it ends, so two runs
#: in one checkout do not meet.
OUT_DIR = os.path.join(ROOT, ".benchmark_out")


def run_dir():
    path = os.path.join(OUT_DIR, "run_%d" % os.getpid())
    os.makedirs(path, exist_ok=True)
    return path


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path):
    with open(os.path.join(ROOT, path)) as fin:
        return json.load(fin)


def load_module(path, name=None):
    """Import a file of the benchmark by its path (names such as
    ``alexnet-227.py`` or ``engine.step_ms.train.py`` are no module
    names)."""
    full = os.path.join(ROOT, path)
    name = name or "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in path)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, full)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


def device_facts(chips, require_tpu=True):
    """The device as JAX reports it; raises NoDevice unless it is a TPU
    with at least ``chips`` chips (``require_tpu=False`` is the CPU
    rehearsal, which prints no device metric)."""
    import jax

    devices = jax.devices()
    facts = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if require_tpu and (facts["platform"] != "tpu"
                        or len(devices) < chips):
        raise NoDevice("the cell needs %d TPU chip(s); JAX reports %d "
                       "device(s) of platform %r"
                       % (chips, len(devices), facts["platform"]))
    return facts


def peaks_for(device_kind):
    table = load_json("benchmark/peaks.json")
    if device_kind not in table:
        raise KeyError("device kind %r is not in benchmark/peaks.json; "
                       "add it with its source" % device_kind)
    return table[device_kind]


def memory_stats():
    """Allocator statistics of the fullest chip ({} where the backend
    reports none, as the CPU does)."""
    import jax

    best = {}
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        if stats.get("peak_bytes_in_use", 0) \
                >= best.get("peak_bytes_in_use", -1):
            best = stats
    return best


class CompileCounter:
    """Counts programs JAX lowers, from JAX's own monitoring events:
    every new (function, shapes) pair is lowered once whether or not
    the persistent cache then holds its binary, so a count that rises
    inside a window means warm-up missed a program. Beside it, how
    set-up was spent: programs the persistent cache held and lacked,
    and the seconds the backend compiled or loaded them in."""

    LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.backend_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kwargs):
        if event == self.LOWERED:
            self.count += 1
        elif event == self.BACKEND:
            self.backend_s += duration

    def _on_event(self, event, **kwargs):
        if event == self.HIT:
            self.cache_hits += 1
        elif event == self.MISS:
            self.cache_misses += 1

    def set_up_marks(self):
        """What set-up's compiling came to, for the run's counters."""
        import jax

        return {"programs_lowered": self.count,
                "backend_compile_s": self.backend_s,
                "compile_cache_hits": self.cache_hits,
                "compile_cache_misses": self.cache_misses,
                "compile_cache_dir": str(
                    jax.config.jax_compilation_cache_dir),
                "compile_cache_max_size": int(
                    jax.config.jax_compilation_cache_max_size)}


class Heartbeat:
    """A thread that sleeps ``interval`` seconds at a time through the
    window and notes how late it woke. A run whose window lost seconds
    then says whether the host stood still with it (the machine, or
    whatever held the interpreter) or kept running while the device or
    the program's own loop did not."""

    def __init__(self, interval=0.02, noted_from=0.1):
        import threading

        self.interval, self.noted_from = interval, noted_from
        self.late = []           # (seconds into the window, seconds late)
        self.worst = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)

    def start(self):
        self.opened = time.perf_counter()
        self._thread.start()

    def _beat(self):
        last = time.perf_counter()
        while not self._stop.wait(self.interval):
            now = time.perf_counter()
            late = now - last - self.interval
            self.worst = max(self.worst, late)
            if late >= self.noted_from:
                self.late.append((round(last - self.opened, 2),
                                  round(late, 3)))
            last = now

    def stop(self):
        """Counters of the window: the worst lateness, and every one
        of a tenth of a second or more as [at, late] in seconds."""
        self._stop.set()
        self._thread.join(timeout=5.0)
        return {"host_late_max_ms": self.worst * 1e3,
                "host_late": json.dumps(self.late[:20])}


class Comparison:
    """The numbers compared with the plain reference, each beside its
    limit. ``correct`` is that every number is within its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        self.rows.append((name, float(value), float(limit)))

    @property
    def correct(self):
        return bool(self.rows) and all(
            value <= limit for _, value, limit in self.rows)

    def as_dict(self):
        return {name: {"value": value, "limit": limit}
                for name, value, limit in self.rows}

    def lines(self):
        return ["compared %s = %.6g (limit %.6g)%s"
                % (name, value, limit,
                   "" if value <= limit else "  <-- OVER")
                for name, value, limit in self.rows]


def relative_gap(value, reference):
    return abs(value - reference) / max(abs(reference), 1e-30)


def worst_leaf_gap(program, reference, keep=None):
    """Worst leaf of |program's norm - reference's norm| over the
    larger of the reference's norm of that leaf and of the median
    leaf. ``keep`` masks leaves that count."""
    import statistics

    floor = statistics.median(reference)
    worst = 0.0
    for i, (got, want) in enumerate(zip(program, reference)):
        if keep is not None and not keep[i]:
            continue
        worst = max(worst, abs(got - want) / max(want, floor, 1e-30))
    return worst


class TracedWindow:
    """A ``jax.profiler`` capture owned by the benchmark: starts the
    capture, has the program's spans written into it as
    ``TraceAnnotation``s, and fails if no ``.xplane.pb`` appears."""

    def __init__(self, name):
        self.dir = os.path.join(run_dir(), "trace_" + name)
        self.path = None
        self._saved = None

    def start(self):
        import jax

        from veles_tpu.observe.tracing import get_tracer

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        tracer = get_tracer()
        self._saved = (tracer.enabled, tracer.annotate_device)
        # no per-call Python events: with some hundred server threads
        # they would swamp the capture; TraceAnnotations are host
        # tracer events and stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        tracer.annotate_device = True
        tracer.enabled = True
        self.started = time.perf_counter()

    def stop(self):
        import jax

        from veles_tpu.observe.tracing import get_tracer

        self.host_seconds = time.perf_counter() - self.started
        tracer = get_tracer()
        tracer.enabled, tracer.annotate_device = self._saved
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no .xplane.pb under "
                               + self.dir)
        self.path = found[0]
        return self.path

    def discard(self):
        shutil.rmtree(self.dir, ignore_errors=True)
