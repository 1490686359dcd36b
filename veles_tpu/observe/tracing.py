"""End-to-end trace propagation: trace_id/span_id context + span events.

The reference VELES correlated its MongoDB event store by session id;
this module upgrades the JSONL event stream (``core/logger.py``) to
proper distributed traces: every span event carries ``trace_id`` /
``span_id`` / ``parent_id`` plus a monotonic clock stamp, serving
requests propagate context via an ``X-Veles-Trace`` header, fleet jobs
carry it as a ``trace`` field in the job/update frames, and
``veles_tpu observe export-trace`` turns the JSONL into a
Perfetto-loadable Chrome ``trace_event`` JSON — one serving request is
followable admission → prefill dispatch → decode chunks → collect
across threads, one fleet job master → slave → apply.

Fast-path contract (the overhead-guard test pins it): a DISABLED tracer
returns one shared null-span singleton from ``span()`` — no allocation,
no id generation, no recorder traffic — so instrumented hot paths
(``ContinuousDecoder``, the unit tick) cost one attribute check when
observability is off.

Cross-thread spans: context propagation uses ``contextvars`` within a
thread; handing a trace to another thread (the serving driver, the
fleet executor) is EXPLICIT — carry ``span.context()`` and pass it as
``parent=`` — because the serving holder/driver handoff predates any
ambient context machinery and must never depend on which thread runs
the continuation.
"""

import contextvars
import os
import random
import threading
import time

from veles_tpu.core.logger import get_event_recorder
from veles_tpu.observe.fleetscope import get_span_ring
from veles_tpu.observe.flight import get_flight_recorder

#: the serving trace header: "<trace_id>/<span_id>" (hex)
TRACE_HEADER = "X-Veles-Trace"

_current = contextvars.ContextVar("veles_trace_span", default=None)


#: ids and the process id without a system call per span: on the
#: chip's host one costs 5-8 us (measured, PERF.md PR 27) and a root
#: span made four. The generator is this module's own (a program that
#: seeds ``random`` does not repeat ids), seeded from the OS once and
#: again in a forked child
_ids = random.Random(os.urandom(16))
_pid = os.getpid()


def _after_fork():
    global _pid
    _ids.seed(os.urandom(16))
    _pid = os.getpid()


os.register_at_fork(after_in_child=_after_fork)


def _new_id():
    return "%016x" % _ids.getrandbits(64)


class NullSpan:
    """The shared disabled-path span: every operation is a no-op and
    ``span()`` hands out THIS singleton (identity asserted by the
    overhead guard), so disabled tracing allocates nothing."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def context(self):
        return None

    def annotate(self, **attrs):
        return self

    def finish(self):
        pass


NULL_SPAN = NullSpan()


class Span:
    """One span: records ``begin``/``end`` events through the
    EventRecorder (session-correlated with the logs, like the
    reference's Mongo events) with trace ids, a wall stamp AND a
    monotonic stamp (``mono`` — what the Chrome exporter orders by),
    and the recording thread (``tid``)."""

    __slots__ = ("tracer", "name", "label", "capture", "trace_id",
                 "span_id", "parent_id", "attrs", "_token", "_finished",
                 "_annotation", "_t0_mono")

    def __init__(self, tracer, name, trace_id, parent_id, label=None,
                 capture=True, **attrs):
        self.tracer = tracer
        self.name = name
        #: what the TraceAnnotation is called in a profiler capture
        #: (the recorded events keep ``name``): ``unit.run`` is one
        #: event name for every unit, ``unit.run.<unit>`` in a capture
        self.label = label or name
        #: False keeps the span out of a profiler capture (it is still
        #: recorded): a span that only waits would cover the gaps the
        #: threads doing the work leave
        self.capture = capture
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.attrs = attrs
        self._token = None
        self._finished = False
        self._annotation = None
        self._t0_mono = None

    def context(self):
        """The (trace_id, span_id) pair to hand across threads or
        processes (header, frame field, holder dict)."""
        return (self.trace_id, self.span_id)

    def annotate(self, **attrs):
        """Attach attributes; they ride the END event (so late facts —
        token counts, outcomes — land on the span)."""
        self.attrs.update(attrs)
        return self

    def _record(self, etype):
        mono = time.monotonic()
        payload = dict(
            name=self.name, etype=etype, trace_id=self.trace_id,
            span_id=self.span_id, parent_id=self.parent_id,
            mono=mono, tid=threading.get_ident(),
            pid=_pid, **self.attrs)
        get_event_recorder().record(**payload)
        # the black box holds the last spans regardless of which
        # EventRecorder instance is active (flight.py; bounded append)
        get_flight_recorder().note_span(payload)
        if etype == "begin":
            self._t0_mono = mono
            return
        # COMPLETED spans (end/single) feed the fleet span ring
        # (observe/fleetscope.py): a fleet slave piggybacks these
        # summaries on its update frames so the master can assemble
        # the cross-process timeline. Disabled ring = one attribute
        # check; the ring itself is bounded and lock-free.
        ring = get_span_ring()
        if ring.enabled:
            t0 = self._t0_mono if etype == "end" \
                and self._t0_mono is not None else mono
            ring.note_span(self.name, self.trace_id, self.span_id,
                           self.parent_id, t0,
                           max(0.0, (mono - t0) * 1000.0),
                           threading.get_ident())

    def __enter__(self):
        self._token = _current.set(self)
        if self.capture and self.tracer.annotate_device:
            # put the span on the device trace's clock: a
            # TraceAnnotation named after it (``label``) is an event
            # of the capture's /host:CPU plane (--profile-dir)
            try:
                import jax
                self._annotation = jax.profiler.TraceAnnotation(
                    self.label)
                self._annotation.__enter__()
            except Exception:
                self._annotation = None
        self._record("begin")
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            self.attrs.setdefault("error", repr(exc))
        self.finish()
        return False

    def finish(self):
        if self._finished:
            return
        self._finished = True
        if self._annotation is not None:
            try:
                self._annotation.__exit__(None, None, None)
            finally:
                self._annotation = None
        self._record("end")
        if self._token is not None:
            try:
                _current.reset(self._token)
            except ValueError:
                pass  # finished on a different thread than it began
            self._token = None


class Tracer:
    """Span factory. Disabled (the default) it returns the shared
    :data:`NULL_SPAN`; enabled it creates real spans that inherit the
    ambient trace (or mint a new trace_id) and flow through the
    EventRecorder to the JSONL file, the web-status timeline and the
    Chrome exporter."""

    def __init__(self, enabled=False):
        self.enabled = enabled
        #: when True (the profiler integration is active), every span
        #: also enters a jax.profiler.TraceAnnotation of its name
        self.annotate_device = False

    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def span(self, name, parent=None, **attrs):
        """Open a span. ``parent`` overrides the ambient context: a
        ``(trace_id, span_id)`` pair (from a header/frame/holder), a
        Span, or None to inherit from this thread's current span.
        ``label=`` names the span's TraceAnnotation in a profiler
        capture where that should say more than ``name``, and
        ``capture=False`` leaves the span out of the capture; neither
        is an attribute of the recorded events."""
        if not self.enabled:
            return NULL_SPAN
        if parent is None:
            ambient = _current.get()
            if ambient is not None and ambient.trace_id is not None:
                parent = (ambient.trace_id, ambient.span_id)
        elif isinstance(parent, Span):
            parent = (parent.trace_id, parent.span_id)
        if parent:
            trace_id, parent_id = parent
        else:
            trace_id, parent_id = _new_id(), None
        return Span(self, name, trace_id, parent_id, **attrs)

    def event(self, name, parent=None, **attrs):
        """A zero-duration span (etype "single"): one recorded point
        with full trace identity — submission stamps, completions."""
        if not self.enabled:
            return NULL_SPAN
        span = self.span(name, parent=parent, **attrs)
        span._record("single")
        span._finished = True
        return span


_tracer = Tracer(enabled=False)


def get_tracer():
    return _tracer


def current_context():
    """This thread's (trace_id, span_id), or None."""
    span = _current.get()
    if span is None or span.trace_id is None:
        return None
    return (span.trace_id, span.span_id)


# -- wire formats ----------------------------------------------------------

def format_trace_header(context):
    """(trace_id, span_id) -> the X-Veles-Trace value."""
    if not context:
        return None
    return "%s/%s" % context


def parse_trace_header(value):
    """X-Veles-Trace value -> (trace_id, span_id) or None. Hostile
    input degrades to None — a garbage header must never 500 a serving
    request."""
    if not value or not isinstance(value, str):
        return None
    trace_id, _, span_id = value.partition("/")
    trace_id = trace_id.strip()
    span_id = span_id.strip()
    if not trace_id or len(trace_id) > 64 or len(span_id) > 64:
        return None
    if not all(c in "0123456789abcdefABCDEF-" for c in trace_id + span_id):
        return None
    return (trace_id, span_id or None)


def parse_trace_field(value):
    """The fleet-frame ``trace`` field ([trace_id, span_id]) -> context
    tuple or None; tolerates wire garbage like the header parser."""
    if not isinstance(value, (list, tuple)) or len(value) != 2:
        return None
    trace_id, span_id = value
    if not isinstance(trace_id, str) or not trace_id:
        return None
    return (trace_id, span_id if isinstance(span_id, str) else None)
