"""Declarative inputs to the analyzer: WHICH code owes WHICH invariant.

Two of the rule families cannot be inferred from syntax alone — they
encode deployment facts about this codebase's threading model:

- **record-path modules/functions** (``lock.record-path``): code on the
  flight-recorder discipline (PRs 10/12) — called from the serving hot
  path, possibly from several threads, and REQUIRED to stay lock-free,
  I/O-free and device-sync-free. Declared here as a mapping from a
  module path *suffix* to the set of function qualnames owing the
  discipline (``None`` = every function in the module).
- **shared classes** (``shared.rmw``): classes whose instances are
  reachable from BOTH the HTTP handler threads and the serving driver
  thread (or the fleet event loop), so attribute mutations must be
  GIL-atomic single ops or run under the class's lock. Declared as a
  mapping from module path suffix to ``{class name: exempt methods}``
  (``__init__`` is always exempt: no concurrency before publication).

To put a NEW module on the record path or declare a NEW shared class,
extend the literals below (or pass ``--record-path`` / ``--shared-class``
to the CLI for a one-off run) — docs/static_analysis.md walks through
both.

Deliberately NOT declared here:

- ``RequestLedger``/``FlightRecorder``/``MetricHistory`` as shared
  classes: they ARE mutated from several threads, but the flight-
  recorder discipline forbids them the lock that would satisfy
  ``shared.rmw`` — their counters are documented best-effort tallies
  (drift under contention is accepted; the bounded containers stay
  consistent because every container op is a single GIL-atomic call).
  Declaring them would make the two rule families contradict each
  other by construction.
"""

import os

#: module-path suffix -> set of "Class.method"/"function" qualnames on
#: the flight-recorder discipline, or None for the whole module
RECORD_PATH_FUNCTIONS = {
    "observe/reqledger.py": None,
    # note/note_span are the per-span record hooks; dump() runs on the
    # (rare) trip path and legitimately takes _dump_lock + writes
    "observe/flight.py": {"FlightRecorder.note",
                          "FlightRecorder.note_span"},
    # the sampler tick runs on the default-on background thread and on
    # deadline-sensitive governor fallbacks; incident writes happen in
    # _check_rules (anomaly firings only), which is NOT declared
    "observe/history.py": {"MetricHistory.maybe_sample",
                           "MetricHistory.sample",
                           "MetricHistory.record_control",
                           "MetricHistory._ingest",
                           "_Series.push"},
    # the fleet goodput observatory: the span ring sits on the slave's
    # span-finish path, the rest on the master's event loop per frame;
    # incident writes live in FleetScope.autopsy_tick, NOT declared
    "observe/fleetscope.py": {"SpanRing.note_span", "SpanRing.drain",
                              "ClockEstimate.observe",
                              "StepWindow.push",
                              "FleetScope.note_issue",
                              "FleetScope.note_update",
                              "FleetScope.book_update"},
    # the serving goodput observatory: every note_* sits on the
    # serving driver's per-dispatch hot path (and inject_waste on the
    # chaos monkey's before_step, same thread); _on_gc runs inside
    # any thread's garbage collection; incident writes live
    # in ServeScope.autopsy_tick, NOT declared
    "observe/servescope.py": {"ServeScope._second",
                              "ServeScope._mark",
                              "ServeScope.note_idle",
                              "_on_gc",
                              "ServeScope.note_admit",
                              "ServeScope.note_dispatch",
                              "ServeScope.note_collect",
                              "ServeScope.inject_waste",
                              "ServeScope.note_slot_admit",
                              "ServeScope.note_slot_first",
                              "ServeScope.note_slot_retire"},
    # the HBM attribution plane: scratch tags sit on the admission
    # handler/resolve paths, the lifecycle-edge snapshots on the
    # driver's rebuild/swap/promote seams, note_pool on the governor
    # tick — all GIL-atomic container ops. MemScope is deliberately
    # NOT a shared class (the FlightRecorder doctrine above: its
    # tallies are best-effort, its containers copy-on-write tuples
    # and bounded deques); incident writes live in flush_incidents,
    # NOT declared
    "observe/memscope.py": {"MemScope.scratch_note",
                            "MemScope.scratch_drop",
                            "MemScope.edge_begin",
                            "MemScope.edge_end",
                            "MemScope.note_pool"},
}

#: module-path suffix -> {class name: (exempt method names,)}; every
#: non-exempt method's read-modify-write attribute mutations must sit
#: under a ``with self.<lock>`` (attribute matching LOCK_ATTR_RE)
SHARED_CLASSES = {
    # handler threads admit/record, the driver resolves
    "serving.py": {"ServingHealth": ()},
    # the HTTP pool gate and the driver share the page pool + cache
    "parallel/kv_pool.py": {"PagePool": (), "PrefixCache": ()},
    # scrape threads read windows the driver/handlers feed
    "observe/slo.py": {"SLOEngine": ()},
    # every thread with a metric to book mutates the registry
    "observe/metrics.py": {"MetricsRegistry": ()},
    # jit wrappers on driver + prefetch threads book compile windows
    "observe/xla_stats.py": {"CompileTracker": ()},
    # router handler threads + attempt threads race inside each Lease;
    # handler threads and the control-plane poller share ElasticRouter
    # tallies
    "router.py": {"Lease": (), "ElasticRouter": ()},
    # router handler threads bump lease/failure tallies on a Replica
    # the poller thread scores (the plane's lifecycle state machine
    # itself is single-writer on the poller thread)
    "fleet/serve_plane.py": {"Replica": ()},
}

#: attribute names treated as locks by lock-nesting/census checks —
#: anchored to underscore/name boundaries so ``blocker``/``clock``
#: are NOT classified as locks (a false lock would silently satisfy
#: shared.rmw and mis-fire the lock rules)
LOCK_ATTR_PATTERN = r"(?:^|_)(?:lock|mutex)(?:_|$)"


class AnalysisRegistry:
    """One run's declarations (the default instance mirrors the
    literals above; tests build their own around fixture files)."""

    def __init__(self, record_path=None, shared_classes=None):
        self.record_path = dict(RECORD_PATH_FUNCTIONS
                                if record_path is None else record_path)
        self.shared_classes = dict(SHARED_CLASSES if shared_classes
                                   is None else shared_classes)

    def add_record_path(self, spec):
        """``PATH_SUFFIX[:func,Class.method,...]`` (CLI seam)."""
        path, _, funcs = spec.partition(":")
        names = {f.strip() for f in funcs.split(",") if f.strip()}
        self.record_path[path] = names or None

    def add_shared_class(self, spec):
        """``PATH_SUFFIX:ClassName`` (CLI seam)."""
        path, sep, cls = spec.partition(":")
        if not sep or not cls:
            raise ValueError(
                "shared-class spec %r is not PATH_SUFFIX:ClassName"
                % spec)
        self.shared_classes.setdefault(path, {})[cls] = ()

    @staticmethod
    def _norm(path):
        return path.replace(os.sep, "/") if os.sep != "/" else path

    @classmethod
    def _matches(cls, path, suffix):
        """Suffix match at a path-SEGMENT boundary: ``serving.py``
        matches ``veles_tpu/serving.py`` but never
        ``samples/llm_serving.py`` (a bare endswith would apply one
        module's declarations to any similarly-named file)."""
        norm = cls._norm(path)
        return norm == suffix or norm.endswith("/" + suffix)

    def record_path_functions(self, path):
        """The declared qualnames for ``path`` (``None`` = whole
        module, ``()`` = not a record-path module)."""
        for suffix, funcs in self.record_path.items():
            if self._matches(path, suffix):
                return funcs
        return ()

    def shared_classes_for(self, path):
        """``{class name: exempt methods}`` declared for ``path``."""
        out = {}
        for suffix, classes in self.shared_classes.items():
            if self._matches(path, suffix):
                out.update(classes)
        return out


DEFAULT_REGISTRY = AnalysisRegistry()
