"""Online inference serving: REST API unit + request-fed loaders.

TPU-native re-design of reference ``veles/restful_api.py:78-215``,
``veles/loader/restful.py:52-140`` and ``veles/loader/interactive.py:57``.
The reference served over Twisted; here the HTTP server is a stdlib
``ThreadingHTTPServer`` on a daemon thread and the workflow loop stays in
the main thread — each handler thread stages its sample, blocks on a
per-request event, and the loader/API pair wakes it with the result after
the forward tick.

Request format (identical to the reference):
``POST <path> {"input": ..., "codec": "list"|"base64"[, "shape": [...],
"type": "float32"]}`` → ``{"result": ...}``.

Batching: requests accumulate into one static-shape minibatch; a tick
fires when the batch is full or ``max_response_time`` elapses with at
least one request staged — so single requests still see bounded latency
while bursts amortize one XLA dispatch across the whole batch (the TPU
translation of the reference's LoopingCall flush).

Survival layer (docs/serving_robustness.md): every HTTP surface carries
a :class:`ServingHealth` exposing ``/healthz`` + ``/readyz``; admission
is bounded (429 + ``Retry-After`` when saturated, 503 while not ready);
requests carry deadlines that free their decoder slot on expiry; and
:class:`GenerateAPI`'s driver is a circuit breaker that sheds, rebuilds
the decoder from the held params with exponential backoff, probes, and
closes again — a device failure degrades service for seconds instead of
wedging the process until a human restarts it.
"""

import base64
import functools
import json
import math
import threading
import time

import numpy

import jax.numpy as jnp

from veles_tpu.core.config import root
from veles_tpu.core.mutable import Bool
from veles_tpu.core.units import Unit
from veles_tpu.loader.base import Loader, TEST, register_loader
from veles_tpu.observe.flight import get_flight_recorder
from veles_tpu.observe.metrics import (bridge, get_metrics_registry,
                                       publish_decoder,
                                       publish_serving_health)
from veles_tpu.observe.history import get_metric_history
from veles_tpu.observe.reqledger import get_request_ledger
from veles_tpu.observe.servescope import get_serve_scope, watch_gc
from veles_tpu.observe.slo import get_slo_engine, observe_request
from veles_tpu.observe.tracing import (NULL_SPAN, TRACE_HEADER,
                                       current_context,
                                       format_trace_header, get_tracer,
                                       parse_trace_header)
from veles_tpu.observe.xla_stats import get_compile_tracker

#: decode host-time histogram buckets (seconds): sub-ms host
#: bookkeeping through multi-second cold-compile dispatches
DECODE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                  0.1, 0.25, 0.5, 1.0, 2.5, 10.0)


@register_loader("restful")
class RestfulLoader(Loader):
    """Minibatches assembled from live HTTP requests (reference
    ``RestfulLoader``, ``loader/restful.py:52``)."""

    def __init__(self, workflow, **kwargs):
        self.sample_shape = tuple(kwargs.pop("sample_shape", ()))
        self.max_response_time = float(kwargs.pop("max_response_time", 0.1))
        if self.max_response_time < 0:
            raise ValueError("max_response_time must be >= 0")
        super().__init__(workflow, **kwargs)
        self.complete = Bool(False)
        self.requests = []

    def init_unpickled(self):
        super().init_unpickled()
        self._event_ = threading.Event()
        self._lock_ = threading.Lock()
        self._staged_data_ = None
        self._staged_requests_ = []

    def derive_from(self, loader):
        """Adopt the trained loader's sample geometry + normalizer so
        served inputs get identical preprocessing (reference
        ``derive_from``)."""
        self.sample_shape = tuple(loader.minibatch_data.shape[1:])
        self.normalizer = getattr(loader, "normalizer", None)

    # -- ILoader --------------------------------------------------------------
    def load_data(self):
        if not self.sample_shape:
            raise ValueError(
                "%s: set sample_shape= or derive_from(trained_loader)"
                % self.name)
        self.class_lengths = [self.max_minibatch_size, 0, 0]
        self._staged_data_ = numpy.zeros(
            (self.max_minibatch_size,) + self.sample_shape, numpy.float32)

    def create_minibatch_data(self):
        mb = self.max_minibatch_size
        self.minibatch_data.reset(numpy.zeros(
            (mb,) + self.sample_shape, numpy.float32))
        self.minibatch_indices.reset(numpy.zeros(mb, numpy.int64))
        self.sample_mask.reset(numpy.zeros(mb, numpy.float32))

    def fill_minibatch(self, indices, valid):
        raise AssertionError("RestfulLoader overrides run()")

    # -- serving loop ---------------------------------------------------------
    def run(self):
        """Block until at least one request is staged (the flush timer or
        a full batch sets the event), then publish the minibatch."""
        # max_response_time=0 means "flush as soon as anything is staged":
        # poll at a small interval rather than waiting forever
        poll = self.max_response_time if self.max_response_time > 0 \
            else 0.01
        while not self._event_.wait(timeout=poll):
            if self.complete:
                return
            with self._lock_:
                if self._staged_requests_:
                    break
        self._event_.clear()
        if self.complete:
            return
        with self._lock_:
            n = len(self._staged_requests_)
            batch = self._staged_data_.copy()
            self.requests = list(self._staged_requests_)
            self._staged_requests_ = []
        normalizer = getattr(self, "normalizer", None)
        if normalizer is not None:
            batch = normalizer.apply_batch(numpy, batch)
        self.minibatch_class = TEST
        self.minibatch_valid_size = n
        self.minibatch_data.data = jnp.asarray(batch)
        self.sample_mask.data = jnp.asarray(
            (numpy.arange(self.max_minibatch_size) < n
             ).astype(numpy.float32))
        self.samples_served += n

    def feed(self, data, request):
        """Called from HTTP handler threads: stage one sample."""
        data = numpy.asarray(data, numpy.float32)
        if data.shape != self.sample_shape:
            data = data.reshape(self.sample_shape)
        with self._lock_:
            slot = len(self._staged_requests_)
            if slot >= self.max_minibatch_size:
                raise OverflowError("minibatch overflow: retry")
            self._staged_data_[slot] = data
            self._staged_requests_.append(request)
            if slot + 1 == self.max_minibatch_size:
                self._event_.set()

    def stop(self):
        self.complete.set(True)
        self._event_.set()


@register_loader("interactive")
class InteractiveLoader(Loader):
    """One-sample serving driven from a REPL: ``loader.feed(obj)``
    (reference ``InteractiveLoader``, ``loader/interactive.py:57``).
    ``feed(None)`` completes the workflow."""

    def __init__(self, workflow, **kwargs):
        self.sample_shape = tuple(kwargs.pop("sample_shape", ()))
        self.loadtxt_kwargs = kwargs.pop("loadtxt_kwargs", {})
        kwargs.setdefault("minibatch_size", 1)
        super().__init__(workflow, **kwargs)
        self.complete = Bool(False)

    def init_unpickled(self):
        super().init_unpickled()
        self._event_ = threading.Event()
        self._food_ = None

    def load_data(self):
        if not self.sample_shape:
            raise ValueError("%s: set sample_shape=" % self.name)
        self.class_lengths = [1, 0, 0]

    def create_minibatch_data(self):
        self.minibatch_data.reset(numpy.zeros(
            (1,) + self.sample_shape, numpy.float32))
        self.minibatch_indices.reset(numpy.zeros(1, numpy.int64))
        self.sample_mask.reset(numpy.ones(1, numpy.float32))

    def fill_minibatch(self, indices, valid):
        raise AssertionError("InteractiveLoader overrides run()")

    def run(self):
        self.info("waiting for feed()...")
        self._event_.wait()
        self._event_.clear()
        if self.complete:
            return
        self.minibatch_class = TEST
        self.minibatch_valid_size = 1
        self.minibatch_data.data = jnp.asarray(
            self._food_.reshape((1,) + self.sample_shape))
        self.samples_served += 1

    def feed(self, obj):
        if obj is None:
            self.complete.set(True)
            self._event_.set()
            return
        if isinstance(obj, str):
            obj = self._load_file(obj)
        self._food_ = numpy.asarray(obj, numpy.float32)
        self._event_.set()

    def _load_file(self, path):
        try:
            loaded = numpy.load(path)
            if hasattr(loaded, "files"):  # npz
                return loaded[loaded.files[0]]
            return loaded
        except Exception:
            return numpy.loadtxt(path, **self.loadtxt_kwargs)


#: weakref to the newest started GenerateAPI (the deploy CLI's target)
_CURRENT_API = None


def get_current_api():
    """This process's live serving api (the newest
    ``GenerateAPI.start()``), or None — ``deploy_cli.rollout_package``
    targets it when no api is injected."""
    return _CURRENT_API() if _CURRENT_API is not None else None


class ServingHealth:
    """Thread-safe health + counter registry shared by the serving HTTP
    surfaces; ``snapshot()`` backs ``/healthz``, the web-status
    dashboard's serving column, and the chaos-suite asserts.

    ``ready`` is the load-balancer signal (``/readyz``): True only while
    the unit can actually take traffic. ``breaker`` is ``closed`` in
    normal operation and ``open`` while :class:`GenerateAPI` rebuilds a
    failed decoder. The counters:

    - ``admitted`` / ``completed`` — requests let in / answered;
    - ``rejected`` — load-shed at admission (429/503), never queued;
    - ``expired`` — deadline hit; the request's decoder slot was freed;
    - ``trips`` / ``rebuilds`` — breaker opened / decoder successfully
      rebuilt and probed;
    - ``shed`` — in-flight requests resolved with an error on a trip
      (they never burn out their full timeout);
    - ``errors`` — requests resolved with any other error.

    Latency accounting: :meth:`record_latency` feeds per-kind rolling
    windows (``ttft`` — staged to first generated token on the host;
    ``tpot`` — time per output token, fed from the chunk collect
    cadence via the request ledger; ``queue_wait`` — staged to
    admitted into a decoder slot), and the snapshot exposes their
    p50/p95 in milliseconds, so the prefill/admission path's cost AND
    the steady-state token cadence are observable on ``/healthz`` and
    the web-status serving column, not just in bench runs."""

    COUNTERS = ("admitted", "completed", "rejected", "expired", "shed",
                "trips", "rebuilds", "errors")
    #: rolling-window latency kinds exposed as p50/p95 on /healthz
    LATENCY_KINDS = ("ttft", "tpot", "queue_wait")
    #: rolling-window size per latency kind
    LATENCY_WINDOW = 512

    def __init__(self, name="serving"):
        import collections

        self.name = name
        self._lock = threading.Lock()
        self._ready = False
        self._breaker = "closed"
        self._inflight = 0
        self._counters = {key: 0 for key in self.COUNTERS}
        self._pool_ref = None
        self._slo_ref = None
        self._governor_ref = None
        self._scope_ref = None
        self._deploy_ref = None
        self._latencies = {
            kind: collections.deque(maxlen=self.LATENCY_WINDOW)
            for kind in self.LATENCY_KINDS}

    @property
    def ready(self):
        with self._lock:
            return self._ready

    def set_ready(self, flag):
        with self._lock:
            self._ready = bool(flag)

    def set_breaker(self, state):
        with self._lock:
            self._breaker = state
        # breaker transitions are exactly what a post-mortem wants in
        # the black box (flight.py; bounded, lock-free append)
        get_flight_recorder().note("breaker", state=state,
                                   api=self.name)

    def incr(self, key, n=1):
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + n

    def counter(self, key):
        """One counter's current value (the request ledger stamps
        ``rebuilds`` as the row's breaker generation)."""
        with self._lock:
            return self._counters.get(key, 0)

    def attach_slo(self, engine):
        """Mirror an SLO engine's worst short-window burn rate into the
        health snapshot (weakly referenced, like the pool) so the
        web-status serving cell shows budget burn beside the survival
        counters."""
        import weakref

        with self._lock:
            self._slo_ref = weakref.ref(engine) if engine is not None \
                else None

    def attach_governor(self, governor):
        """Mirror the serving governor's tier/actuation state into the
        health snapshot and let it price this surface's Retry-After
        (weakly referenced, like the pool and the SLO engine)."""
        import weakref

        with self._lock:
            self._governor_ref = weakref.ref(governor) \
                if governor is not None else None

    def attach_servescope(self, scope):
        """Mirror the serving goodput observatory's occupancy /
        goodput / waste-share summary into the health snapshot
        (weakly referenced, like the pool and the SLO engine) so
        ``/healthz`` and the web-status serving cell answer
        "occupancy N% · goodput N%" beside the survival counters."""
        import weakref

        with self._lock:
            self._scope_ref = weakref.ref(scope) if scope is not None \
                else None

    def retry_after_s(self, need=1):
        """The honest Retry-After price for this surface's 429/503s,
        in seconds clamped [1, 60]: the attached governor's price
        first (it watches the pool release rate AND the degradation
        state), else the pool's release-rate pricing, else 1 — the
        ``core/httpd.py:retry_after_headers`` source contract."""
        with self._lock:
            governor = self._governor_ref() \
                if self._governor_ref is not None else None
            pool = self._pool_ref() if self._pool_ref is not None \
                else None
        if governor is not None:
            return governor.retry_after_s(need)
        if pool is not None:
            return pool.retry_after(need)
        return 1.0

    def attach_deploy(self, api):
        """Mirror the deploy state — the serving weights' version
        stamp and, while a blue-green rollout is live, its
        ``snapshot()`` — into the health snapshot (weakly referenced,
        like the pool) so ``/healthz`` answers "which weights, and is
        a rollout ramping" (docs/zero_downtime.md)."""
        import weakref

        with self._lock:
            self._deploy_ref = weakref.ref(api) if api is not None \
                else None

    def attach_pool(self, pool):
        """Mirror a paged KV pool's occupancy/prefix-cache state into
        the health snapshot (weakly referenced — a rebuilt decoder's
        fresh pool re-attaches, a dead one silently drops out), so
        ``/healthz``, the web-status serving column and the chaos
        asserts see page pressure next to the survival counters."""
        import weakref

        with self._lock:
            self._pool_ref = weakref.ref(pool) if pool is not None \
                else None

    def try_admit(self, limit, pool_gate=None):
        """One atomic admission decision: returns ``None`` and counts
        the request in, or the rejection kind (``"unready"`` -> 503,
        ``"full"`` -> 429) — checked and booked under one lock so a
        burst cannot race past the queue bound. ``limit`` of ``None``
        or <= 0 means UNBOUNDED admission (load shedding off).

        ``pool_gate`` extends the decision to KV page pressure: a
        zero-arg callable returning ``None`` (pages reserved, admit)
        or a retry-after in seconds (pool full — the caller 429s with
        ``Retry-After`` priced from the observed page-release rate,
        not a constant). It runs under the admission lock AFTER the
        queue bound, so a reservation is only ever made for a request
        that is otherwise admitted — the no-deadlock invariant: every
        admitted request has its worst-case page demand reserved, so
        it can never block forever on pages it was promised."""
        with self._lock:
            if not self._ready:
                self._counters["rejected"] += 1
                return "unready"
            if limit is not None and limit > 0 \
                    and self._inflight >= limit:
                self._counters["rejected"] += 1
                return "full"
            if pool_gate is not None:
                retry_after = pool_gate()
                if retry_after is not None:
                    self._counters["rejected"] += 1
                    return ("pool", retry_after)
            self._inflight += 1
            self._counters["admitted"] += 1
            return None

    def release(self, outcome="completed"):
        """Book one admitted request out (``completed`` / ``expired`` /
        ``shed`` / ``errors``)."""
        with self._lock:
            self._inflight = max(0, self._inflight - 1)
            self._counters[outcome] = self._counters.get(outcome, 0) + 1

    def reject_admitted(self):
        """Roll an admission back as a rejection: RESTfulAPI discovers
        saturation only when ``feed`` overflows, AFTER try_admit — the
        request books as rejected-never-admitted so the counter
        identity ``admitted == completed+expired+shed+errors+inflight``
        holds on both surfaces."""
        with self._lock:
            self._inflight = max(0, self._inflight - 1)
            self._counters["admitted"] -= 1
            self._counters["rejected"] += 1

    @property
    def inflight(self):
        with self._lock:
            return self._inflight

    def record_latency(self, kind, seconds):
        """Feed one sample into the ``kind`` rolling window (seconds;
        unknown kinds get a window on first use)."""
        import collections

        with self._lock:
            if kind not in self._latencies:
                self._latencies[kind] = collections.deque(
                    maxlen=self.LATENCY_WINDOW)
            self._latencies[kind].append(float(seconds))

    @staticmethod
    def _percentiles_ms(values):
        if not values:
            return {"p50": None, "p95": None, "count": 0}
        ordered = sorted(values)
        n = len(ordered)
        p50 = ordered[(n - 1) // 2]
        p95 = ordered[min(n - 1, int(math.ceil(0.95 * (n - 1))))]
        return {"p50": round(p50 * 1000.0, 3),
                "p95": round(p95 * 1000.0, 3), "count": n}

    def snapshot(self, seconds=True):
        """The ``/healthz`` payload. ``seconds=False`` leaves out the
        goodput observatory's per-second books: ``/metrics``
        (``publish_serving_health``) takes its gauges from this
        snapshot at every scrape, the metric history's sampler's once
        a second among them, and has no use for a 256-second ring
        copied and rounded each time."""
        with self._lock:
            snap = {"name": self.name, "ready": self._ready,
                    "breaker": self._breaker,
                    "inflight": self._inflight,
                    "counters": dict(self._counters),
                    "latency_ms": {
                        kind: self._percentiles_ms(window)
                        for kind, window in self._latencies.items()}}
            pool = self._pool_ref() if self._pool_ref is not None \
                else None
            slo = self._slo_ref() if self._slo_ref is not None \
                else None
            governor = self._governor_ref() \
                if self._governor_ref is not None else None
            scope = self._scope_ref() if self._scope_ref is not None \
                else None
            deploy = self._deploy_ref() \
                if self._deploy_ref is not None else None
        if deploy is not None:
            snap["version"] = getattr(deploy, "version", None)
            layout = getattr(getattr(deploy, "decoder", None),
                             "kv_layout", None)
            if layout is not None:
                # the dense slab's device layout (decode.py
                # slot_layout_facts): which layout this run is in
                snap["kv_layout"] = layout
            if getattr(getattr(deploy, "decoder", None), "moe_load",
                       None) is not None:
                # the routed experts' load, among the counters
                snap["counters"].update(deploy.decoder.moe_counters())
            holds = getattr(getattr(deploy, "decoder", None),
                            "slot_holds", None)
            if holds is not None:
                # the blocks by kind and a slot's two kinds of state
                snap["counters"].update(holds)
            paths = getattr(getattr(deploy, "decoder", None),
                            "attend_paths", None)
            if paths is not None:
                # the dense slab's decode dispatches by how they attend
                snap["counters"]["attend_path"] = dict(paths)
            paths = getattr(getattr(deploy, "decoder", None),
                            "state_paths", None)
            if paths is not None:
                # ... and by how they take the retention state through
                snap["counters"]["state_path"] = dict(paths)
            chunks = getattr(getattr(deploy, "decoder", None),
                             "kda_prompt_chunks", None)
            if chunks is not None:
                # the admissions' chunks through the delta rule's form
                snap["counters"]["kda_prompt_chunks"] = chunks
            paths = getattr(getattr(deploy, "decoder", None),
                            "write_paths", None)
            if paths is not None:
                # ... and by how they write the chunk's blocks
                snap["counters"]["block_write_path"] = dict(paths)
            paths = getattr(getattr(deploy, "decoder", None),
                            "prompt_paths", None)
            if paths is not None:
                # the admissions by how their grouped blocks attend the
                # prompts, and the prompts past a window
                snap["counters"]["prompt_attend_path"] = dict(paths)
                snap["counters"]["admits_past_window"] = \
                    deploy.decoder.admits_past_window
            rollout = getattr(deploy, "_rollout", None)
            if rollout is not None:
                snap["rollout"] = rollout.snapshot()
        if pool is not None:
            snap["pool"] = pool.snapshot()
        if scope is not None:
            summary = scope.summary()
            if summary is not None:
                snap["servescope"] = summary
            if seconds:
                # the driver's books by wall second (servescope.py
                # SECOND_FIELDS), what the benchmark's scheduler.*
                # readers sum over a window
                snap["counters"]["serve_seconds"] = scope.second_rows()
        if slo is not None:
            summary = slo.summary()
            if summary is not None:
                snap["slo"] = summary
        if governor is not None:
            snap["governor"] = governor.snapshot()
        # the HBM attribution cell: the LIGHT summary only (top tagged
        # owners, headroom forecast, leak tally) — the reconciled
        # device scan stays on /metrics and /debug/memory, not on
        # every /healthz poll
        try:
            from veles_tpu.observe.memscope import get_memscope
            memscope = get_memscope().summary()
            if memscope.get("tagged_bytes"):
                snap["memscope"] = memscope
        except Exception:
            pass
        return snap


class RESTfulAPI(Unit):
    """HTTP inference endpoint (reference ``RESTfulAPI``,
    ``restful_api.py:78-215``).

    Wire-up: ``api.link_attrs(loader, "feed", "requests",
    "minibatch_valid_size")`` and ``api.results = forward_output_array``;
    place it after the last forward in the control loop."""

    VIEW_GROUP = "SERVICE"
    #: handler threads give up after this long without a tick
    RESPONSE_TIMEOUT = 60.0

    def __init__(self, workflow, **kwargs):
        self.port = int(kwargs.pop("port", root.common.api.get("port",
                                                               8180)))
        self.path = kwargs.pop("path",
                               root.common.api.get("path", "/api"))
        # loopback by default — same posture as the fleet server
        self.host = kwargs.pop("host",
                               root.common.api.get("host", "127.0.0.1"))
        if not self.path.startswith("/"):
            raise ValueError("path must start with '/'")
        self.max_body = int(kwargs.pop("max_body", 0)) or None
        super().__init__(workflow, **kwargs)
        self.results = None
        self.demand("feed", "requests")

    def init_unpickled(self):
        super().init_unpickled()
        self._httpd_ = None
        # trailing underscore: volatile (holds a Lock — must be
        # excluded from pickles and rebuilt on unpickle)
        self.health_ = ServingHealth(name="restful-api")

    @property
    def health(self):
        """Survival-layer health surface (``/healthz``/``readyz``)."""
        return self.health_

    def initialize(self, **kwargs):
        from http.server import BaseHTTPRequestHandler
        from veles_tpu.core.httpd import (MAX_BODY, BodyTooLarge,
                                          QuietHandlerMixin,
                                          enable_metrics, read_body,
                                          serve_debug_history,
                                          serve_debug_index,
                                          serve_debug_memory,
                                          serve_debug_requests,
                                          serve_debug_serve,
                                          serve_health, serve_metrics,
                                          start_server)

        api = self
        limit = self.max_body or MAX_BODY
        bridge(enable_metrics(), self.health, publish_serving_health)

        class Handler(QuietHandlerMixin, BaseHTTPRequestHandler):
            def do_POST(self):
                if self.path != api.path:
                    self.send_error(404)
                    return
                try:
                    raw = read_body(self, limit=limit)
                except BodyTooLarge:
                    return  # 413 already sent, nothing buffered
                with get_tracer().span(
                        "restful.request",
                        parent=parse_trace_header(
                            self.headers.get(TRACE_HEADER))):
                    api.serve(self, raw)

            def do_GET(self):
                if serve_metrics(self):
                    return
                if serve_debug_requests(self):
                    return
                if serve_debug_history(self):
                    return
                if serve_debug_serve(self):
                    return
                if serve_debug_memory(self):
                    return
                if serve_debug_index(self):
                    return
                if not serve_health(self, api.health):
                    self.send_error(404)

        self._httpd_, self.port = start_server(
            Handler, self.host, self.port, name="restful-api")
        self.health.set_ready(True)
        self.info("listening on %s:%d%s", self.host, self.port, self.path)

    def stop(self):
        self.health.set_ready(False)
        if self._httpd_ is not None:
            self._httpd_.shutdown()
            self._httpd_ = None

    # -- request side (handler threads) ---------------------------------------
    def _fail(self, handler, message):
        from veles_tpu.core.httpd import reply
        self.warning(message)
        reply(handler, {"error": message}, code=400)

    def _decode(self, handler, payload):
        codec = payload.get("codec")
        if codec == "list":
            try:
                return numpy.asarray(payload["input"], numpy.float32)
            except (ValueError, TypeError) as exc:
                self._fail(handler, "invalid input array: %s" % exc)
                return None
        if codec != "base64":
            self._fail(handler, "codec must be 'list' or 'base64'")
            return None
        shape = payload.get("shape")
        dtype = payload.get("type")
        if not isinstance(shape, list) or not shape or dtype is None:
            self._fail(handler, "base64 codec needs 'shape' and 'type'")
            return None
        try:
            buf = base64.b64decode(payload["input"])
            return numpy.frombuffer(
                buf, numpy.dtype(dtype)).reshape(shape).astype(
                numpy.float32)
        except Exception as exc:
            self._fail(handler, "failed to decode: %s" % exc)
            return None

    def serve(self, handler, raw):
        try:
            payload = json.loads(raw.decode())
        except ValueError:
            self._fail(handler, "failed to parse JSON")
            return
        if not isinstance(payload, dict) or "input" not in payload \
                or "codec" not in payload:
            self._fail(handler, "need 'input' and 'codec' attributes")
            return
        data = self._decode(handler, payload)
        if data is None:
            return
        from veles_tpu.core.httpd import reply
        # the request-truth row (observe/reqledger.py): this surface
        # has no slot-engine waterfall, but its requests still land in
        # /debug/requests and the black box with staged -> resolved
        # stamps and an outcome
        ctx = current_context()
        ledger = get_request_ledger()
        row = ledger.stage(api="restful-api",
                           trace=ctx[0] if ctx else None,
                           prompt_len=int(getattr(data, "size", 0)))
        # the same atomic admit/release pair as GenerateAPI, so the
        # /healthz inflight gauge and counters stay balanced here too
        # (the queue bound itself is the minibatch: feed overflows)
        from veles_tpu.core.httpd import retry_after_headers
        if self.health.try_admit(None) is not None:
            ledger.resolve(row, "rejected", error="not ready")
            reply(handler, {"error": "not ready"}, code=503,
                  headers=retry_after_headers(self.health))
            return
        responder = {"event": threading.Event(), "result": None}
        try:
            self.feed(data, responder)
        except OverflowError:
            # admission control: the serving minibatch is full — shed
            # with a retry hint instead of queueing unboundedly (the
            # batch flushes within max_response_time, so the priced
            # helper's 1 s floor stays honest here)
            self.health.reject_admitted()
            ledger.resolve(row, "rejected", error="saturated")
            reply(handler, {"error": "server saturated: retry"},
                  code=429, headers=retry_after_headers(self.health))
            return
        except Exception as exc:
            self.health.release("errors")
            ledger.resolve(row, "errors", error=str(exc))
            self._fail(handler, "invalid input: %s" % exc)
            return
        if not responder["event"].wait(self.RESPONSE_TIMEOUT):
            # a server-side stall is retryable — 503, matching the
            # GenerateAPI surface, never a client-blaming 400
            self.health.release("expired")
            ledger.resolve(row, "expired", error="inference timed out")
            self.warning("inference timed out")
            reply(handler, {"error": "inference timed out"}, code=503,
                  headers=retry_after_headers(self.health))
            return
        self.health.release("completed")
        ledger.resolve(row, "completed")
        reply(handler, {"result": responder["result"]})

    # -- response side (workflow thread, after the forward tick) --------------
    def run(self):
        if self.results is None:
            return
        out = numpy.asarray(getattr(self.results, "mem", self.results))
        for i, responder in enumerate(self.requests):
            if responder is None:
                continue
            value = out[i]
            responder["result"] = (value.tolist()
                                   if isinstance(value, numpy.ndarray)
                                   else float(value))
            responder["event"].set()


def build_serve_mesh(spec):
    """Build the SERVING mesh from ``--serve-mesh`` /
    ``root.common.serve.mesh``: an ``AXIS=N[,AXIS=N...]`` string (the
    shared ``--mesh`` parser; -1 absorbs the remaining devices), a
    dict of axis sizes, or None/"" (no mesh — single-chip serving, the
    default). Validation errors name the flag, not a reshape frame;
    sizes are validated by ``build_mesh`` itself (a 2.5 must raise,
    never silently truncate to 2).

    The serve mesh is built from ALL-1 axes plus exactly what the spec
    names — never seeded from the TRAINING config
    (``root.common.mesh.axes``): a pod-training ``data=2`` leaking into
    ``--serve-mesh model=4`` would silently replicate the slot engine's
    compute and HBM across the data axis (or blame the serve flag for a
    device-count mismatch it didn't cause)."""
    if not spec:
        return None
    from veles_tpu.parallel.mesh import AXIS_ORDER, build_mesh, parse_axes

    if isinstance(spec, str):
        spec = parse_axes(spec, flag="--serve-mesh")
    elif hasattr(spec, "__content__"):
        spec = spec.__content__()
    spec = dict(spec)
    if not spec:
        return None  # an empty config subtree configures nothing
    axes = {name: 1 for name in AXIS_ORDER}
    axes.update(spec)
    return build_mesh(flag="root.common.serve.mesh / --serve-mesh",
                      **axes)


class ContinuousDecoder:
    """Continuous-batching LLM serving on the slot engine
    (``parallel/decode.py`` ``init_slot_state``/``slot_admit_many``/
    ``slot_step``): a fixed pool of KV-cache slots decodes in lockstep
    while new requests prefill into free slots MID-FLIGHT — no
    generation restarts, no waiting for the batch to drain (the
    beyond-reference serving tier; VELES's analogue batched per tick,
    ``restful_api.py:78-215``).

    Host-side single-threaded driver: call :meth:`submit` any time,
    then :meth:`step` repeatedly (or :meth:`run_until_drained`); each
    step admits queued requests into free slots and advances every
    active slot by one token. Greedy by default, ``temperature > 0``
    samples per request from ``fold_in(base_key, request_id)``;
    per-request token budget ``n_tokens`` (or per-submit override),
    optional ``eos`` token that retires a sequence early. Tokens stream
    into ``results[request_id]`` as they are generated.

    The hot path keeps per-step cost proportional to ACTUAL sequence
    state (docs/serving_performance.md): admission prefills are
    bucket-shaped and every queued same-bucket prompt admits in one
    ``slot_admit_many`` dispatch; attention is tiled to the longest
    live sequence (``tile``, default 128); ``quantize=`` plumbs the
    int8 weight / int8-KV serving tiers into the slot pool; and
    :meth:`dispatch_chunk` / :meth:`collect_chunk` split a chunk's
    enqueue from its readback so callers (:meth:`drain_pipelined`, the
    :class:`GenerateAPI` driver) overlap the host round trip with
    device compute.

    Numerical contract: a request's stream equals single-request
    ``generate()``'s math-for-math (same sublayer fns, same per-step
    sampling keys) — asserted exactly on CPU. On TPU, batching S slots
    changes XLA's matmul tiling vs a batch-1 run, so logits can wobble
    at the 1e-2 level and near-tied argmaxes may break differently;
    trained models (clear logit margins) are unaffected, random-weight
    toys can diverge at ties."""

    def __init__(self, params, embed_table, heads, slots=4,
                 max_len=512, n_tokens=32, eos=None,
                 temperature=0.0, top_k=0, key=None, quantize=None,
                 tile=None, mesh=None, mesh_axis="model", paged=False,
                 page_size=None, pool_pages=None,
                 prefix_cache=None, aot=None, ledger=None):
        import collections

        import jax

        from veles_tpu.ops.platform import on_tpu
        from veles_tpu.parallel.blocks import (arch_of, expert_blocks,
                                               require_gpt2)
        from veles_tpu.parallel.decode import (SLOT_SPAN_TILE,
                                               init_slot_state,
                                               quantize_params,
                                               shard_slot_params,
                                               span_tile)

        if quantize not in (None, "none", "int8", "int8-kv"):
            raise ValueError("quantize must be None, 'int8' or "
                             "'int8-kv', got %r" % (quantize,))
        #: the model's own architecture (parallel/blocks.py: it rides
        #: in params["arch"]; a tree without one is GPT-2's block). The
        #: dense slab serves every kind; a tier that is built on
        #: GPT-2's leaves refuses another kind by name, here, before
        #: anything is placed on the device
        arch = arch_of(params)
        for asked, tier, lacks, which in (
                (paged, "paged=True (the page pool)",
                 "pages hold k/v rows of heads x head_dim: no latent "
                 "row, no fewer K/V heads, no fixed state beside them",
                 "paged"),
                (quantize not in (None, "none"),
                 "quantize=%r" % (quantize,),
                 "quantize_params and the int8 cache know GPT-2's "
                 "matrices and k/v leaves", "int8"),
                (mesh is not None, "mesh= (tensor-parallel serving)",
                 "slot_param_specs and slot_state_specs shard GPT-2's "
                 "leaves over heads", "mesh"),
                (aot is not None, "aot= (exported programs)",
                 "a bundle's geometry describes one k/v slab", None),
                (prefix_cache is not None,
                 "prefix_cache= (prefix reuse over pages)",
                 "a cached prefix is its pages: a fixed state would "
                 "have to be snapshot with them", "prefix")):
            if asked:
                require_gpt2(params, tier, lacks, which)
        #: quantize="int8" serves the W8A16 tier (weight matrices int8,
        #: dequant fused into the products via matmul_any);
        #: "int8-kv" additionally stores the SLOT KV cache as int8 with
        #: per-(position, head) scales — the same machinery as
        #: generate(quantize=...), plumbed into continuous batching
        self.quantize = quantize if quantize != "none" else None
        if self.quantize and not isinstance(params["head"], dict):
            params = quantize_params(params)
        #: serving mesh (docs/sharded_serving.md): params go
        #: tensor-parallel over ``mesh_axis``, the slot KV shards over
        #: heads, and every dispatch below runs the SAME slot programs
        #: under the sharded layout (one compiled program per layout —
        #: token streams stay identical to the single-chip engine).
        #: Quantization above ran on the FULL weights, so the int8
        #: payload each shard holds is bit-identical to single-chip.
        self.mesh = mesh
        self.mesh_axis = mesh_axis
        if mesh is not None:
            params, embed_table = shard_slot_params(
                params, embed_table, heads, mesh, axis=mesh_axis)
        self.params = params
        self.embed_table = embed_table
        #: the last hot-swap's reshard receipt ({"bytes", "seconds",
        #: "counts"} — parallel/reshard.py) or None before any swap /
        #: off-mesh; the deploy surfaces expose it so a train->serve
        #: transition can be PINNED slice-only (0 wire bytes)
        self.last_swap_stats = None
        self.heads = heads
        self.slots = slots
        if self.quantize == "int8-kv":
            # whole lane tiles: T is the lane dimension of the int8-KV
            # head-major layout (masking keeps the extra positions
            # inert)
            max_len = -(-max_len // SLOT_SPAN_TILE) * SLOT_SPAN_TILE
        self.max_len = max_len
        #: attended-span tile: each dispatch attends over
        #: ceil((longest live sequence + chunk)/tile)*tile positions
        #: instead of max_len — one compiled program per tile count
        #: (``decode.span_tile``: 128, wider past 2,048 positions)
        self.tile = int(tile if tile is not None else span_tile(max_len))
        if self.tile < 1:
            raise ValueError("tile must be >= 1, got %d" % self.tile)
        #: paged KV pool (docs/paged_kv.md): the slab becomes a page
        #: pool + host page table, prefix reuse becomes an admission
        #: path. ``pool_pages`` defaults to the slab-equivalent HBM
        #: (slots x ceil((max_len + 2*n_tokens)/page_size) plus the
        #: scratch page — the 2*n_tokens term covers the lag-1
        #: pipeline's dispatch overshoot for any chunk <= n_tokens);
        #: sizing it independently of slots x max_len is the point —
        #: concurrency is then bounded by LIVE tokens, not the slab.
        self.paged = bool(paged)
        self.page_size = int(page_size if page_size is not None
                             else SLOT_SPAN_TILE) if paged else None
        if paged and self.page_size < 1:
            raise ValueError("page_size must be >= 1, got %d"
                             % self.page_size)
        if paged and self.page_size % SLOT_SPAN_TILE and on_tpu():
            # gathered paged spans are pages x page_size; the attend
            # kernel gates lanes at SLOT_SPAN_TILE granules on TPU, so
            # a misaligned page size surfaces as an opaque XLA tiling
            # failure deep in the first dispatch — fail at construction
            # with the knob's name instead
            raise ValueError(
                "page_size/--serve-page-size must be a multiple of "
                "SLOT_SPAN_TILE (%d) on TPU, got %d"
                % (SLOT_SPAN_TILE, self.page_size))
        if paged:
            from veles_tpu.parallel.kv_pool import default_pool_pages
            # the default covers dispatch chunks up to n_tokens (a
            # chunk larger than any request's budget buys nothing);
            # drivers chunking past that must size pool_pages
            self.pool_pages = (int(pool_pages)
                               if pool_pages is not None else
                               default_pool_pages(slots, max_len,
                                                  self.page_size,
                                                  chunk=n_tokens))
        else:
            self.pool_pages = None
        #: fused-kernel tier (docs/paged_kv.md "The fused kernel"):
        #: whether paged dispatches attend through the fused kernel
        #: (ops/paged_attention.py) instead of the page-table gather;
        #: admission groups then go RAGGED — page-rounded widths, no
        #: pow2 row duplication. A fact read off the module's rule,
        #: the same one the device fn reads at trace time.
        if paged:
            from veles_tpu.ops.paged_attention import use_paged_kernel
            self.paged_kernel = use_paged_kernel(mesh)
        else:
            self.paged_kernel = False
        self.n_tokens = n_tokens
        self.eos = eos
        #: temperature > 0 samples; each request draws from its OWN
        #: key stream fold_in(base_key, request_id), so its tokens
        #: equal generate(batch=1, key=that key) regardless of which
        #: slot it lands in or who shares the batch
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.base_key = key if key is not None else jax.random.key(0)
        n_blocks = len(params["blocks"])
        embed = embed_table.shape[1]
        vocab = embed_table.shape[0]
        build_state = functools.partial(
            init_slot_state, n_blocks, slots, self.max_len, heads,
            embed // heads, vocab, dtype=embed_table.dtype,
            quantized=self.quantize == "int8-kv",
            mesh=mesh, mesh_axis=mesh_axis, paged=self.paged,
            pages=self.pool_pages, page_size=self.page_size, arch=arch)
        #: the dense slab's device layout, decided once: the layout
        #: the chunk program works in, taken from the compiler on one
        #: representative chunk and pinned on every slot program from
        #: then on (parallel/decode.py decide_slot_formats). Where the
        #: state's programs are not this process's to compile (an AOT
        #: bundle: jax.export carries no layout) and off the TPU (the
        #: CPU's compiler has one layout to choose from) the leaves
        #: keep the default, and nothing else differs.
        formats = None
        platform = (jax.default_backend() if mesh is None
                    else mesh.devices.flat[0].platform)
        if not self.paged and aot is None and platform == "tpu":
            from veles_tpu.parallel.decode import decide_slot_formats
            # representative: a chunk of 8 steps over half the lane
            formats = decide_slot_formats(
                self.params, self.embed_table, heads,
                jax.eval_shape(build_state), 8,
                min(self.max_len,
                    -(-(self.max_len // 2) // self.tile) * self.tile),
                mesh=mesh, mesh_axis=mesh_axis)
            build_state = functools.partial(build_state,
                                            formats=formats)
        self.state = build_state()
        #: which layout this decoder runs in, as its K/V leaves report
        #: it (major_to_minor, tiling, the state's device bytes): said
        #: once per run, in /healthz and on the first decode.dispatch
        #: span. The page pool's is kv_pool's own affair.
        self.kv_layout = None
        #: what a slot holds, among ``/healthz``'s counters: the
        #: model's blocks by kind, and a slot's bytes as rows a
        #: position and as fixed state (parallel/decode.py
        #: slot_holds); None for the page pool
        self.slot_holds = None
        if not self.paged:
            from veles_tpu.parallel.decode import (slot_holds,
                                                   slot_layout_facts)
            self.kv_layout = slot_layout_facts(self.state)
            self.slot_holds = slot_holds(params, self.state)
        #: the routed experts' books (None for a model without): what
        #: the decode chunks counted (decode._slot_steps emits, beside
        #: the tokens, each expert block's assignments per expert at
        #: each step). ``assignments`` per (expert block, expert), and
        #: by the number of live slots a chunk ran with
        #: ``[block-steps, assignments, experts touched]``: how many
        #: experts a step reads depends on how many tokens it routes
        #: ``paths``: dispatches (chunks, steps and admissions alike)
        #: by the tiling their grouped products took, which the
        #: program's shapes decide when it is traced
        #: (``ops/moe.expert_plan``); ``slices``: the same dispatches by
        #: the slices of the inner width their kernel took an expert in
        #: (1: whole, as ``ragged_dot`` takes it)
        self.moe_load = None
        if expert_blocks(params):
            self.moe_load = {"assignments": None, "by_lanes": {},
                             "paths": {"streamed": 0, "tiled": 0,
                                       "grouped": 0}, "slices": {}}
        #: the dense slab's decode dispatches (chunks and single
        #: steps) by how their program attends the cache: ``kernel``
        #: (each slot over its own length, ops/slab_attention.py) or
        #: ``xla`` (every slot over the span), which the state's
        #: leaves and the platform decide when the program is traced
        #: (``parallel/decode.slot_attend_path``). None for the page pool,
        #: whose attend is ``paged_kernel``'s affair.
        self.attend_paths = None if self.paged \
            else {"kernel": 0, "xla": 0}
        #: the same books for the fixed state of the retention blocks
        #: (``parallel/decode.slot_state_path``: the Pallas kernel of
        #: ops/retention.py or ``jax.numpy``); None for a model
        #: without such a block
        self.state_paths = None
        #: the same books by how the program writes a chunk's staged
        #: blocks to the slab (``parallel/decode.slot_write_path``:
        #: ``kernel``, ops/slab_write.py, or ``loop``, a write per slot
        #: and leaf); None for the page pool and for a model with no
        #: row a position
        self.write_paths = None
        #: whether any block keeps a row a position. A model whose
        #: blocks all carry a fixed state has no window to attend: one
        #: step program whatever its slots hold, and no overshoot
        self._has_rows = self.paged
        if not self.paged:
            from veles_tpu.parallel.decode import (_kv_names,
                                                   slot_state_path)
            self._has_rows = bool(_kv_names(self.state))
            if self._has_rows:
                self.write_paths = {"kernel": 0, "loop": 0}
            if slot_state_path(params, self.state) is not None:
                self.state_paths = {"kernel": 0, "xla": 0}
        #: the admissions by how their prompts attend in the blocks of
        #: grouped heads with no head norm (``blocks.prompt_attend_path``:
        #: the splash ``kernel`` or ``xla``), and the requests whose
        #: prompt was longer than a window block's window; None for a
        #: model without such blocks
        self.prompt_paths = None
        self.admits_past_window = 0
        #: chunks of ``ops/delta_rule.CHUNK`` positions that admissions
        #: ran through the delta rule's chunked form, over the ``"kda"``
        #: blocks (``blocks.prompt_chunks``); None for a model without
        #: such blocks
        self.kda_prompt_chunks = None
        if not self.paged:
            from veles_tpu.parallel.blocks import (prompt_attend_path,
                                                   prompt_chunks)
            if prompt_attend_path(params, 1, 16, heads) is not None:
                self.prompt_paths = {"kernel": 0, "xla": 0}
            if prompt_chunks(params, 1, 16) is not None:
                self.kda_prompt_chunks = 0
        self._layout_said = False
        self.pool = None
        self._paged_fns = None
        self._slot_pages = {}    # slot -> [page id, ...] logical order
        if self.paged:
            from veles_tpu.parallel.kv_pool import (PagePool,
                                                    paged_restore,
                                                    sharded_paged_fns)
            self.pool = PagePool(self.pool_pages, self.page_size,
                                 cache=prefix_cache)
            if mesh is not None:
                self._paged_fns = sharded_paged_fns(
                    mesh, mesh_axis,
                    quantized=self.quantize == "int8-kv")
            if prefix_cache is not None and len(prefix_cache):
                # breaker-rebuild path: the previous decoder's prefix
                # cache restores into THIS pool by page copy — never a
                # re-prefill (re-prefilling every cached prompt after
                # a trip would defeat the cache)
                restore = (self._paged_fns[5] if self._paged_fns
                           else paged_restore)
                self.state = self.pool.restore_entries(self.state,
                                                       restore)
        # the dense slot programs are resolved per call from the
        # module (late binding — the chaos/fault-injection seam tests
        # patch), which pins them to where and how this state's K/V
        # leaves lie, single-chip and sharded alike (decode.slot_fns):
        # a donated state never drifts off its layout and every
        # (bucket, group) compiles exactly once
        #: AOT compiled-program bundle (docs/aot_artifacts.md): a
        #: loaded ``veles_tpu.aot.loader.AotPrograms`` whose bound
        #: facade serves every covered (bucket, group, span) dispatch
        #: from pre-compiled StableHLO — ZERO retracing, the live jit
        #: caches never grow. A geometry mismatch refuses the bundle
        #: with the stale field named and degrades to live compilation
        #: (never a wrong-answer execute); uncovered shapes fall back
        #: per dispatch and count in veles_aot_misses_total.
        self.aot = None
        self._aot = None
        if aot is not None:
            from veles_tpu.aot.loader import AotCompatError
            try:
                self._aot = aot.bind(self)
                self.aot = aot
            except AotCompatError as exc:
                import logging
                logging.getLogger("ContinuousDecoder").warning(
                    "AOT bundle refused (stale field %r): %s — "
                    "serving continues with live compilation",
                    exc.field, exc)
        self._queue = collections.deque()
        self._free = list(range(slots))
        self._slot_req = {}      # slot -> request id
        self._slot_len = {}      # slot -> device-side sequence length
        self._budget = {}        # request id -> tokens still wanted
        self.results = {}        # request id -> [token, ...]
        self.admitted_at = {}    # request id -> monotonic admit stamp
        self._next_id = 0
        #: deploy identity (docs/zero_downtime.md): the version tag
        #: these weights serve under (hot-swap / rollout stamps it)
        #: and the blue-green role ("green" on a rollout's candidate
        #: engine) — the chaos bad-deploy profiles and the ledger's
        #: version stamping key off both
        self.version = None
        self.rollout_role = None
        self.steps = 0
        self.tokens_out = 0
        self.cancelled = 0
        #: jitted-dispatch tally on the slot path — the CI hook the
        #: regression tests assert on (one "admit" per bucket group,
        #: one "chunk" per slot_step_many)
        self.dispatch_counts = {"admit": 0, "admit_requests": 0,
                                "chunk": 0, "step": 0}
        if self.paged:
            # the two prefix-reuse admission families (the dense keys
            # stay byte-identical for dense artifacts)
            self.dispatch_counts["admit_tail"] = 0
            self.dispatch_counts["admit_hit"] = 0
        #: set to a list to trace the dispatch/collect interleaving:
        #: entries ("admit", bucket, group), ("dispatch", chunk),
        #: ("collect", chunk) — the lag-1 pipelining assert hook
        self.dispatch_log = None
        #: observability plane (docs/observability.md): disabled-path
        #: calls are structural no-ops, so the hot path stays the
        #: PR-3 hot path until someone mounts /metrics or a tracer
        self.metrics = get_metrics_registry()
        self._tracer = get_tracer()
        #: the always-on black box: dispatch entries land in its
        #: bounded ring so a breaker trip can dump the tail that led
        #: to it (flight.py — one flag check + append per dispatch)
        self.flight = get_flight_recorder()
        #: the serving goodput observatory (observe/servescope.py):
        #: every admit/step/dispatch books its live vs padded vs
        #: duplicate rows, span/page overshoot and dead-slot
        #: lane-steps into the process scope — bounded, lock-free,
        #: one flag check per dispatch (the flight-ring discipline);
        #: breaker-rebuilt decoders keep accounting into the same
        #: scope (rids carry over, so the slot timeline never
        #: cross-talks); the process's garbage collections book into
        #: its per-second rows beside the driver's
        self.scope = get_serve_scope()
        watch_gc()
        #: request-truth plane (observe/reqledger.py): when a ledger is
        #: attached (GenerateAPI wires the process ledger; rebuilds
        #: re-attach via _decoder_kwargs), every dispatch books its
        #: stage mark + aot/live attribution onto the rows of the
        #: requests it served. None (the default) keeps the hot path
        #: at one attribute check per dispatch — the NULL-path guard
        self.ledger = ledger
        #: rid -> ledger row, scoped to THIS decoder (two engines with
        #: independent rid counters can share one process ledger);
        #: entries pop at retirement/cancel so it is bounded by live
        #: requests plus the admission queue
        self._ledger_rows = {}
        #: device-truth plane: chunk cadence feeds the online MFU
        #: gauge once /metrics is mounted (observe/xla_stats.py)
        self._xla = get_compile_tracker()
        self._last_chunk_done = None
        self._trace = {}  # request id -> (trace_id, span_id) context
        #: recently-retired trace contexts, bounded: the lag-1 pipeline
        #: collects a request's LAST chunk one pass after it retires,
        #: and that collect's span must still attach to the request's
        #: trace instead of rooting an orphan
        self._done_trace = collections.OrderedDict()
        #: per-owner HBM attribution (observe/memscope.py): this
        #: decoder's pytrees report under named owners. The paged KV
        #: leaves live in ``self.state`` but BELONG to the pool —
        #: page_bytes is stamped here and decode_state subtracts the
        #: pool's share, so the two owners split one pytree without
        #: double-counting. Registration is weakref'd: a decoder the
        #: breaker replaces drops out when GC takes it — and a RETAINED
        #: zombie keeps reporting, which is exactly how the lifecycle
        #: edge diff names the leaked owner.
        try:
            from veles_tpu.observe.memscope import get_memscope
            from veles_tpu.parallel.decode import (param_tree_bytes,
                                                   slot_state_bytes)
            scope = get_memscope()
            scope.register(
                "params", self,
                lambda dec: param_tree_bytes(dec.params,
                                             dec.embed_table))
            if self.pool is not None:
                from veles_tpu.parallel.kv_pool import paged_kv_bytes
                self.pool.page_bytes = (paged_kv_bytes(self.state)
                                        // self.pool.pages)
                scope.register("kv_pool", self.pool,
                               lambda pool: pool.hbm_bytes())
                scope.register("prefix_shadows", self.pool,
                               lambda pool: pool.shadow_bytes())
                scope.register(
                    "decode_state", self,
                    lambda dec: max(0, slot_state_bytes(dec.state)
                                    - dec.pool.hbm_bytes()))
            else:
                scope.register(
                    "decode_state", self,
                    lambda dec: slot_state_bytes(dec.state))
        except Exception:
            pass

    def _span(self, name, rids, **attrs):
        """A span parented to the first TRACED request among ``rids``
        (batch-level dispatches serve many requests; one of them owns
        the span, all of them ride its ``rids`` attr). Disabled-path:
        the shared null span, with the parent lookup skipped."""
        if not self._tracer.enabled:
            return NULL_SPAN
        parent = next((self._trace[r] for r in rids
                       if r in self._trace), None)
        if parent is None:
            parent = next((self._done_trace[r] for r in rids
                           if r in self._done_trace), None)
        return self._tracer.span(name, parent=parent,
                                 rids=list(rids), **attrs)

    def _dispatch_attribution(self, fn, default):
        """(program_name, aot_served) of the dispatch that just ran —
        the request ledger's per-dispatch attribution. AOT-bound
        decoders read the facade's last-dispatch record (the program it
        actually served or live-fell-back on); live decoders read the
        instrumented callable's program name."""
        if self._aot is not None:
            last = getattr(self._aot, "last_dispatch", None)
            if last is not None:
                return last
        from veles_tpu.parallel.decode import dispatch_program
        return dispatch_program(fn, default), False

    def ledger_link(self, rid, row):
        """Bind a staged ledger row to request ``rid`` for the
        dispatch-time hooks (GenerateAPI calls this right after
        ``submit``; direct drivers may too)."""
        if self.ledger is None or row is None:
            return
        self.ledger.link(row, rid)
        self._ledger_rows[rid] = row

    def _retire_trace(self, rid):
        trace = self._trace.pop(rid, None)
        if trace is not None:
            self._done_trace[rid] = trace
            while len(self._done_trace) > 4 * self.slots + 8:
                self._done_trace.popitem(last=False)

    def swap_params(self, new_params, new_embed_table=None):
        """Live weight hot-swap (docs/zero_downtime.md): replace the
        weights IN PLACE — slots, pools, compiled programs and the
        request-id counter all survive; only the parameter leaves
        change. The checkpoint arrives in whatever layout it was
        saved in (typically the train layout); on a serving mesh it
        moves onto the live leaves' exact serve placement via
        :func:`~veles_tpu.parallel.reshard.reshard` (pure data
        movement — bit-exact, arxiv 2112.01075), so every compiled
        program keeps its layout contract without retracing.

        Caller contract (``GenerateAPI._apply_swap``): the decoder is
        IDLE — drained behind the breaker's drain-then-swap seam —
        and the caller keeps the returned ``(old_params,
        old_embed_table)`` pair as the one-slot rollback stash (a
        failed probe decode on the new weights restores it through
        this same method, an identity reshard). The prefix cache is
        flushed HERE: cached pages hold KV bytes computed under the
        OLD weights.

        Raises ValueError when the checkpoint's tree structure, leaf
        shapes or dtypes do not match the serving params — a
        mismatched swap would invalidate every compiled program, so
        it is refused up front (the ACT capability-gate lesson) and
        the old weights keep serving."""
        import jax

        from veles_tpu.parallel.decode import quantize_params

        if self.quantize and not isinstance(new_params["head"], dict):
            # quantize the FULL weights before any placement — the
            # constructor's order, so each shard's int8 payload is
            # bit-identical to a cold boot on the same checkpoint
            new_params = quantize_params(new_params)
        new_table = (new_embed_table if new_embed_table is not None
                     else self.embed_table)
        old_leaves, old_tree = jax.tree.flatten(
            (self.params, self.embed_table))
        new_leaves, new_tree = jax.tree.flatten(
            (new_params, new_table))
        if old_tree != new_tree:
            raise ValueError(
                "swap refused: checkpoint tree structure does not "
                "match the serving params (%s vs %s)"
                % (new_tree, old_tree))
        paths = jax.tree_util.tree_flatten_with_path(
            (self.params, self.embed_table))[0]
        for (path, old_leaf), new_leaf in zip(paths, new_leaves):
            if tuple(old_leaf.shape) != tuple(new_leaf.shape) \
                    or old_leaf.dtype != new_leaf.dtype:
                raise ValueError(
                    "swap refused: leaf %s is %s%s in the checkpoint "
                    "but %s%s live — a mismatched swap would "
                    "invalidate every compiled program"
                    % (jax.tree_util.keystr(path), new_leaf.dtype,
                       tuple(new_leaf.shape), old_leaf.dtype,
                       tuple(old_leaf.shape)))
        if self.mesh is not None:
            # train -> serve layout transition: target each live
            # leaf's exact placement, so sharded swap tokens equal
            # single-chip swap tokens and no program recompiles
            from veles_tpu.parallel.reshard import reshard
            dst = jax.tree.unflatten(
                old_tree, [leaf.sharding.spec for leaf in old_leaves])
            (new_params, new_table), stats = reshard(
                (new_params, new_table), self.mesh, dst, label="swap")
            # the transition's wire receipt: a host (train-layout)
            # checkpoint onto a serve mesh must be slice-only — 0
            # bytes on the wire (pinned in test_deploy.py)
            self.last_swap_stats = stats
        else:
            self.last_swap_stats = None
        old = (self.params, self.embed_table)
        self.params = new_params
        self.embed_table = new_table
        if self.pool is not None:
            self.pool.flush_prefix_cache()
        return old

    def submit(self, prompt_tokens, n_tokens=None, trace=None):
        """Queue one prompt (1-D int sequence); returns the request id.
        The prompt is admitted into a slot on a later :meth:`step` when
        one is free. ``trace`` optionally carries the submitting
        request's (trace_id, span_id) so the slot-engine dispatch spans
        connect to it (docs/observability.md)."""
        prompt = numpy.asarray(prompt_tokens, numpy.int32).reshape(-1)
        budget = n_tokens if n_tokens is not None else self.n_tokens
        if len(prompt) + budget > self.max_len:
            raise ValueError(
                "prompt %d + n_tokens %d exceeds max_len %d"
                % (len(prompt), budget, self.max_len))
        rid = self._next_id
        self._next_id += 1
        self._queue.append((rid, prompt, budget))
        self.results[rid] = []
        self._budget[rid] = budget
        if trace is not None:
            self._trace[rid] = trace
        return rid

    @property
    def busy(self):
        return bool(self._queue or self._slot_req)

    @property
    def aot_active(self):
        """True while dispatches resolve through a bound AOT bundle."""
        return self._aot is not None

    def done(self, rid):
        """True once request ``rid``'s stream is complete (its tokens
        sit in ``results[rid]``)."""
        return rid in self.results and rid not in self._budget

    def cancel(self, rid):
        """Abort an incomplete request wherever it is — the admission
        queue or an active slot — freeing the slot immediately and
        reaping its ``results`` entry (an expired-deadline request must
        not burn a slot for its remaining budget, nor leak its token
        list). Safe mid-chunk: collect/step skip a rid with no budget,
        and the freed cache lane is fully overwritten on the next admit.
        Returns True when the request existed and was still running."""
        if rid not in self._budget:
            return False
        for i, queued in enumerate(self._queue):
            if queued[0] == rid:
                del self._queue[i]
                break
        else:
            for slot, owner in list(self._slot_req.items()):
                if owner == rid:
                    del self._slot_req[slot]
                    self._free.append(slot)
                    self._release_slot_pages(slot)
                    self.scope.note_slot_retire(rid,
                                                reason="cancelled")
                    break
        del self._budget[rid]
        self.results.pop(rid, None)
        self.admitted_at.pop(rid, None)
        self._ledger_rows.pop(rid, None)
        self._retire_trace(rid)
        self.cancelled += 1
        return True

    @staticmethod
    def _bucket(n):
        """Prompt-length bucket: next power of two (min 16). Admission
        right-pads to the bucket so XLA compiles ONE prefill program
        per bucket instead of one per distinct prompt length (a fresh
        multi-second compile per request would stall every in-flight
        slot)."""
        bucket = 16
        while bucket < n:
            bucket *= 2
        return bucket

    def bucket_for(self, n):
        """The admission bucket an ``n``-token prompt (or tail)
        actually prefills under: the power-of-two bucket, at least the
        model's ``Arch.prompt_bucket``, clamped to ``max_len`` — ONE
        definition for the admit paths, the page-reservation bound and
        the request ledger's attribution."""
        from veles_tpu.parallel.blocks import arch_of

        return min(max(self._bucket(n), arch_of(self.params).prompt_bucket),
                   self.max_len)

    def _admit_pending(self):
        if self.paged:
            return self._admit_pending_paged()
        return self._admit_pending_dense()

    def _admit_pending_dense(self):
        """Admit every queued request that fits a free slot — grouped
        by prompt bucket, ONE ``slot_admit_many`` dispatch per bucket
        group (the pre-batched path issued one blocking dispatch per
        request on the driver thread). Groups are padded to a
        power-of-two size with duplicate rows so the compile count
        stays O(buckets x log2(slots))."""
        import jax

        from veles_tpu.parallel.blocks import arch_of
        from veles_tpu.parallel.decode import (admit_rows, prefill_parts,
                                               slot_admit_many)

        admit = (self._aot.admit if self._aot is not None
                 else slot_admit_many)
        if not (self._queue and self._free):
            return
        # a bucket's prompts in groups of at most admit_rows each
        groups = {}
        while self._queue and self._free:
            rid, prompt, _ = self._queue.popleft()
            slot = self._free.pop()
            bucket = self.bucket_for(len(prompt))
            held = groups.setdefault(bucket, [[]])
            if len(held[-1]) == admit_rows(arch_of(self.params), bucket):
                held.append([])
            held[-1].append((rid, prompt, slot))
        now = time.monotonic()
        for bucket, group in ((bucket, group) for bucket, held
                              in groups.items() for group in held):
            rows = self._pad_group(group)
            prompts = numpy.zeros((len(rows), bucket), numpy.int32)
            for j, (_, prompt, _) in enumerate(rows):
                prompts[j, :len(prompt)] = prompt
            rids = jnp.asarray([r[0] for r in rows], jnp.int32)
            req_keys = jax.vmap(jax.random.fold_in,
                                in_axes=(None, 0))(self.base_key, rids)
            x = self.embed_table[jnp.asarray(prompts)]
            # the feed-forward sees a part of the group at a time
            parts = prefill_parts(arch_of(self.params), len(rows), bucket)
            said = self._book_moe_path(len(rows) * bucket // parts)
            said.update(self._book_prompt_path(
                len(rows) // parts, bucket, [len(r[1]) for r in group]))
            if self.kda_prompt_chunks is not None:
                from veles_tpu.parallel.blocks import prompt_chunks
                said["kda_prompt_chunks"] = prompt_chunks(
                    self.params, len(rows), bucket)
                self.kda_prompt_chunks += said["kda_prompt_chunks"]
            if self.state_paths is not None:
                # the path the state this admission sets will take
                said["state_path"] = self._state_path()
            # span entered OUTSIDE the timed window: the span's own
            # begin/end writes (file I/O when tracing) must not inflate
            # the host-overhead attribution they exist to explain
            with self._span("decode.admit", [r[0] for r in group],
                            bucket=bucket, group=len(group), **said):
                t0 = time.perf_counter()
                self.state = admit(
                    self.params, self.embed_table, self.heads,
                    self.state,
                    jnp.asarray([r[2] for r in rows], jnp.int32), x,
                    req_keys,
                    jnp.asarray([len(r[1]) for r in rows], jnp.int32))
                elapsed = time.perf_counter() - t0
            self.metrics.observe(
                "veles_decode_admit_seconds", elapsed,
                buckets=DECODE_BUCKETS,
                help="host-blocking bucket-prefill dispatch time")
            self.dispatch_counts["admit"] += 1
            self.dispatch_counts["admit_requests"] += len(group)
            self.flight.note("admit", bucket=bucket, group=len(group),
                             ms=round(elapsed * 1000, 3))
            self._note_scope_admit("dense", bucket, len(group),
                                   len(rows),
                                   [len(r[1]) for r in group], elapsed)
            if self.dispatch_log is not None:
                self.dispatch_log.append(("admit", bucket, len(group)))
            if self.ledger is not None:
                program, aot_served = self._dispatch_attribution(
                    admit, "decode.admit")
                for rid, _, _ in group:
                    self.ledger.note_admit(
                        self._ledger_rows.get(rid), "dense",
                        group=len(group), bucket=bucket,
                        aot=aot_served, program=program)
            for rid, prompt, slot in group:
                self._slot_req[slot] = rid
                self._slot_len[slot] = len(prompt)
                self.admitted_at[rid] = now
                self.scope.note_slot_admit(slot, rid, "dense",
                                           bucket=bucket,
                                           trace=self._trace.get(rid))

    # -- paged admission (docs/paged_kv.md) -------------------------------
    def _note_scope_admit(self, kind, bucket, group, rows, lens,
                          elapsed):
        """ONE copy of the goodput observatory's admission-waste
        booking — the dense path and the paged ``_book_admit``
        families share it, so the live/pad/duplicate decomposition
        can never drift between engines. ``rows`` = padded group
        size, ``lens`` = live prompt/tail lengths (empty for hit
        admissions, which dispatch zero tokens)."""
        if not self.scope.enabled:
            return
        from veles_tpu.parallel.decode import admit_waste
        live, pad, dup = admit_waste(bucket, lens, rows)
        self.scope.note_admit(kind, bucket, group, rows, live, pad,
                              dup, elapsed)

    def _book_admit(self, kind, elapsed, group, bucket, rows=None,
                    lens=None):
        """Shared admission bookkeeping: metrics, flight ring,
        dispatch log, the goodput observatory's waste decomposition
        (``rows`` = padded group size, ``lens`` = live prompt/tail
        lengths; a hit admission dispatches zero tokens) — one copy
        for the cold/tail/hit families."""
        lens = lens if lens is not None else []
        self._note_scope_admit(kind, bucket, len(group),
                               rows if rows is not None
                               else len(group), lens, elapsed)
        self.metrics.observe(
            "veles_decode_admit_seconds", elapsed,
            buckets=DECODE_BUCKETS, labels={"kind": kind},
            help="host-blocking admission dispatch time")
        self.dispatch_counts[
            "admit" if kind == "cold" else "admit_" + kind] += 1
        self.dispatch_counts["admit_requests"] += len(group)
        self.flight.note("admit", family=kind, bucket=bucket,
                         group=len(group),
                         ms=round(elapsed * 1000, 3))
        if self.dispatch_log is not None:
            self.dispatch_log.append(
                ("admit" if kind == "cold" else "admit_" + kind,
                 bucket, len(group)))

    @staticmethod
    def _pad_group(group):
        """Pad an admission group to a power-of-two size with
        duplicate rows (duplicate scatter writes carry equal values —
        the dense engine's compile-bounding idiom)."""
        padded_n = 1
        while padded_n < len(group):
            padded_n *= 2
        return group + [group[-1]] * (padded_n - len(group))

    def _admit_pending_paged(self):
        """The paged admission path: each queued request is classified
        against the prefix cache — ``hit`` (whole prompt cached:
        control rows only, ~0 admission), ``tail`` (page-aligned
        prefix cached: prefill only the unique tail against the pooled
        prefix), or ``cold`` (full bucket prefill scattered into fresh
        pages) — then dispatched in ONE program per (kind, shape)
        group. Page allocation failures (even after LRU eviction)
        requeue the request at the FRONT and stop admitting: pool
        pressure backs up into the queue, never into a torn slot. The
        int8-KV tier reuses exact prompts only (its pool stores
        rounded K/V — partial-hit tails would break bit-identity)."""
        import jax

        from veles_tpu.parallel import kv_pool

        if self._aot is not None:
            admit = self._aot.paged_admit
            admit_tail = self._aot.paged_admit_tail
            admit_hit = self._aot.paged_admit_hit
        else:
            fns = self._paged_fns
            admit = fns[0] if fns else kv_pool.paged_admit_many
            admit_tail = fns[1] if fns else kv_pool.paged_admit_tail
            admit_hit = fns[2] if fns else kv_pool.paged_admit_hit
        if not (self._queue and self._free):
            return
        ps = self.pool.page_size
        allow_partial = self.quantize != "int8-kv"
        cold, tails, hits = {}, {}, []
        cold_order, tail_order = [], []
        while self._queue and self._free:
            rid, prompt, budget = self._queue[0]
            entry, shared = self.pool.lookup(prompt,
                                             allow_partial=allow_partial)
            if entry is not None and shared == len(prompt):
                self._queue.popleft()
                slot = self._free.pop()
                self.pool.book_hit()
                hits.append((rid, prompt, slot, entry))
                continue
            if entry is not None:
                # kernel path: tails group ragged under one key per
                # prefix length (bucket 0 sentinel) and each row
                # allocates EXACTLY its tail's pages — the pow2 bucket
                # ladder only exists to bound the gather path's jit
                # cache
                tail_len = len(prompt) - shared
                tail_bucket = (0 if self.paged_kernel
                               else self.bucket_for(tail_len))
                pages = self.pool.alloc(kv_pool.pages_for(
                    tail_len if self.paged_kernel else tail_bucket, ps))
                if pages is None:
                    self.pool.unlookup(entry)
                    break
                self._queue.popleft()
                slot = self._free.pop()
                self.pool.book_hit()
                key = (len(entry["pages"]), tail_bucket)
                if key not in tails:
                    tails[key] = []
                    tail_order.append(key)
                tails[key].append((rid, prompt, slot, entry, shared,
                                   pages))
                continue
            bucket = (0 if self.paged_kernel
                      else self.bucket_for(len(prompt)))
            pages = self.pool.alloc(kv_pool.pages_for(
                len(prompt) if self.paged_kernel else bucket, ps))
            if pages is None:
                break
            self._queue.popleft()
            slot = self._free.pop()
            self.pool.book_miss()
            if bucket not in cold:
                cold[bucket] = []
                cold_order.append(bucket)
            cold[bucket].append((rid, prompt, slot, pages))
        now = time.monotonic()

        def fold_keys(rows):
            rids = jnp.asarray([r[0] for r in rows], jnp.int32)
            return jax.vmap(jax.random.fold_in,
                            in_axes=(None, 0))(self.base_key, rids)

        for bucket in cold_order:
            group = cold[bucket]
            if self.paged_kernel:
                # ragged admission: ONE dispatch at the group's
                # page-rounded max width — per-row live lengths mask
                # the residual inside the device fn, so there is no
                # pow2 row duplication and no bucket pad beyond the
                # last partial page. Compile variants stay bounded:
                # (rows, width) ranges over slots x page multiples,
                # the same ladder the gather path's buckets walk.
                rows = group
                bucket = kv_pool.pages_for(
                    max(len(r[1]) for r in rows), ps) * ps
            else:
                rows = self._pad_group(group)
            prompts = numpy.zeros((len(rows), bucket), numpy.int32)
            for j, (_, prompt, _, _) in enumerate(rows):
                prompts[j, :len(prompt)] = prompt
            x = self.embed_table[jnp.asarray(prompts)]
            # ragged rows own different page counts: short rows pad
            # with the scratch page (garbage-by-definition, never
            # visible behind the per-row length mask). Gather-path
            # groups allocate uniformly, so the fill is total there.
            n_pages = max(len(r[3]) for r in rows)
            page_ids = numpy.full((len(rows), n_pages),
                                  kv_pool.SCRATCH_PAGE, numpy.int32)
            for j, (_, _, _, pg) in enumerate(rows):
                page_ids[j, :len(pg)] = pg
            with self._span("paged.admit", [r[0] for r in group],
                            bucket=bucket, group=len(group)):
                t0 = time.perf_counter()
                self.state = admit(
                    self.params, self.embed_table, self.heads,
                    self.state,
                    jnp.asarray([r[2] for r in rows], jnp.int32),
                    jnp.asarray(page_ids), x,
                    fold_keys(rows),
                    jnp.asarray([len(r[1]) for r in rows], jnp.int32))
                elapsed = time.perf_counter() - t0
            self._book_admit("cold", elapsed, group, bucket,
                             rows=len(rows),
                             lens=[len(r[1]) for r in group])
            if self.ledger is not None:
                program, aot_served = self._dispatch_attribution(
                    admit, "paged.admit")
            for rid, prompt, slot, pages in group:
                self._slot_req[slot] = rid
                self._slot_len[slot] = len(prompt)
                self._slot_pages[slot] = list(pages)
                self.admitted_at[rid] = now
                self.scope.note_slot_admit(slot, rid, "cold",
                                           bucket=bucket,
                                           trace=self._trace.get(rid))
                if self.ledger is not None:
                    self.ledger.note_admit(
                        self._ledger_rows.get(rid), "cold",
                        group=len(group), bucket=bucket,
                        aot=aot_served, program=program,
                        pages=len(self._slot_pages[slot]))
                # publish the prompt's whole pages (and, when the
                # prompt is page-aligned, its last-position logits)
                # so the NEXT admission of this prefix is a hit
                self.pool.insert(prompt, pages, self.state,
                                 logits=self.state["logits"][slot])
        for key in tail_order:
            pp, tail_bucket = key
            group = tails[key]
            if self.paged_kernel:
                # ragged tails: same doctrine as cold — page-rounded
                # max tail width, per-row tail pages scratch-padded
                # (prefix pages are uniform within the key, which
                # keeps pp in it)
                rows = group
                tail_bucket = kv_pool.pages_for(
                    max(len(r[1]) - r[4] for r in rows), ps) * ps
            else:
                rows = self._pad_group(group)
            tail_tokens = numpy.zeros((len(rows), tail_bucket),
                                      numpy.int32)
            for j, (_, prompt, _, _, shared, _) in enumerate(rows):
                tail = prompt[shared:]
                tail_tokens[j, :len(tail)] = tail
            tail_x = self.embed_table[jnp.asarray(tail_tokens)]
            n_tail = max(len(r[5]) for r in rows)
            tail_pages = numpy.full((len(rows), n_tail),
                                    kv_pool.SCRATCH_PAGE, numpy.int32)
            for j, r in enumerate(rows):
                tail_pages[j, :len(r[5])] = r[5]
            with self._span("paged.admit_tail", [r[0] for r in group],
                            bucket=tail_bucket, group=len(group),
                            prefix_pages=pp):
                t0 = time.perf_counter()
                self.state = admit_tail(
                    self.params, self.embed_table, self.heads,
                    self.state,
                    jnp.asarray([r[2] for r in rows], jnp.int32),
                    jnp.asarray([r[3]["pages"] for r in rows],
                                jnp.int32),
                    jnp.asarray(tail_pages),
                    tail_x, fold_keys(rows),
                    jnp.asarray([len(r[1]) for r in rows], jnp.int32))
                elapsed = time.perf_counter() - t0
            self._book_admit("tail", elapsed, group, tail_bucket,
                             rows=len(rows),
                             lens=[len(r[1]) - r[4] for r in group])
            if self.ledger is not None:
                program, aot_served = self._dispatch_attribution(
                    admit_tail, "paged.admit_tail")
            for rid, prompt, slot, entry, shared, pages in group:
                self._slot_req[slot] = rid
                self._slot_len[slot] = len(prompt)
                self._slot_pages[slot] = list(entry["pages"]) \
                    + list(pages)
                self.admitted_at[rid] = now
                self.scope.note_slot_admit(slot, rid, "tail",
                                           bucket=tail_bucket,
                                           trace=self._trace.get(rid))
                if self.ledger is not None:
                    self.ledger.note_admit(
                        self._ledger_rows.get(rid), "tail",
                        group=len(group), bucket=tail_bucket,
                        aot=aot_served, program=program,
                        pages=len(self._slot_pages[slot]))
                # publish the EXTENDED prompt too (prefix pages + the
                # tail's whole pages hold exactly a cold prefill's
                # bytes — the tail ran the same math behind the
                # prefix-offset mask), so a repeated extended prompt
                # converges to a hit instead of re-prefilling its
                # tail forever
                self.pool.insert(prompt, self._slot_pages[slot],
                                 self.state,
                                 logits=self.state["logits"][slot])
        if hits:
            group = hits
            rows = self._pad_group(group)
            with self._span("paged.admit_hit", [r[0] for r in group],
                            group=len(group)):
                t0 = time.perf_counter()
                self.state = admit_hit(
                    self.state,
                    jnp.asarray([r[2] for r in rows], jnp.int32),
                    jnp.asarray([len(r[1]) for r in rows], jnp.int32),
                    jnp.stack([r[3]["logits"] for r in rows]),
                    fold_keys(rows))
                elapsed = time.perf_counter() - t0
            self._book_admit("hit", elapsed, group, 0,
                             rows=len(rows))
            if self.ledger is not None:
                program, aot_served = self._dispatch_attribution(
                    admit_hit, "paged.admit_hit")
            for rid, prompt, slot, entry in group:
                self._slot_req[slot] = rid
                self._slot_len[slot] = len(prompt)
                self._slot_pages[slot] = list(entry["pages"])
                self.admitted_at[rid] = now
                self.scope.note_slot_admit(slot, rid, "hit",
                                           trace=self._trace.get(rid))
                if self.ledger is not None:
                    self.ledger.note_admit(
                        self._ledger_rows.get(rid), "hit",
                        group=len(group), bucket=0,
                        aot=aot_served, program=program,
                        pages=len(self._slot_pages[slot]))

    def _release_slot_pages(self, slot):
        """Return a retired/cancelled slot's pages to the pool (shared
        prefix pages just drop the slot's ref; the cache's own refs
        keep them resident)."""
        if self.pool is None:
            return
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self.pool.release(pages)

    def _ensure_tail_pages(self, extra):
        """Pre-map every page the next dispatch's appends can touch:
        each live slot's table must cover its length plus ``extra``
        positions (appends never consult the free list in-program).
        Raises when the pool cannot satisfy even after eviction —
        unreachable behind the reservation-gated HTTP admission
        (docs/paged_kv.md), loud for direct drivers."""
        from veles_tpu.parallel.kv_pool import pages_for

        ps = self.pool.page_size
        for slot in self._slot_req:
            need = pages_for(self._slot_len[slot] + extra, ps)
            have = len(self._slot_pages.get(slot) or ())
            if need > have:
                got = self.pool.alloc(need - have)
                if got is None:
                    raise RuntimeError(
                        "kv page pool exhausted mid-decode (%d pages, "
                        "%d free): raise pool_pages/--serve-pool-pages "
                        "or admit through GenerateAPI's pool-aware "
                        "gate" % (self.pool.capacity,
                                  self.pool.free_pages))
                self._slot_pages.setdefault(slot, []).extend(got)

    def _page_table_array(self, extra):
        """The (slots, PB) page-table operand for the next dispatch:
        PB pages cover the longest live sequence plus ``extra``
        appends (the pages-per-slot bucket — one compiled program per
        PB, the paged analogue of the span tile). Rows of freed lanes
        stay scratch so their harmless writes never touch live
        pages."""
        from veles_tpu.parallel.kv_pool import pages_for

        self._ensure_tail_pages(extra)
        ps = self.pool.page_size
        pb = max(pages_for(self._slot_len[s] + extra, ps)
                 for s in self._slot_req)
        table = numpy.zeros((self.slots, pb), numpy.int32)
        for slot in self._slot_req:
            pages = self._slot_pages[slot][:pb]
            table[slot, :len(pages)] = pages
        return jnp.asarray(table)

    def worst_case_pages(self, prompt_len, budget, chunk=1):
        """Upper bound on the pages one request can hold at once —
        what the pool-aware admission gate reserves, so the sum over
        admitted requests never exceeds the pool (the no-deadlock
        invariant). The max over the admission families:

        - cold: the prompt bucket, grown to the token budget plus the
          lag-1 pipeline's two chunks of slack;
        - tail, at every possible page-aligned split: the shared
          prefix's whole pages (the slot refs pin them) PLUS the
          re-bucketed tail — which can exceed the cold bound when
          bucket rounding/clamping make ``pages(prefix) +
          pages(tail_bucket) > pages(prompt_bucket)``."""
        from veles_tpu.parallel.kv_pool import pages_for

        ps = self.page_size
        bucket = self.bucket_for(prompt_len)
        worst = pages_for(bucket + budget + 2 * chunk, ps)
        for shared in range(ps, prompt_len, ps):
            tail_bucket = self.bucket_for(prompt_len - shared)
            worst = max(worst,
                        shared // ps + pages_for(tail_bucket, ps))
        return worst

    def _attended_span(self, extra):
        """Static attended span for the next dispatch: the longest
        LIVE sequence plus the ``extra`` positions the dispatch will
        append, rounded up to the tile (one compiled program per tile
        count) and clamped to ``max_len``; 0 for a model that keeps
        no row a position (nothing is attended: one program)."""
        if not self._has_rows:
            return 0
        longest = max(self._slot_len[s] for s in self._slot_req)
        span = -(-(longest + extra) // self.tile) * self.tile
        return int(min(span, self.max_len))

    def _attend_overshoot(self, lens, chunk, span, pages, slab_kernel):
        """Attended positions past the live slots' sequences in one
        decode dispatch of ``chunk`` steps, for the waste plane: a
        kernel that walks each slot's own length (the paged one its
        live pages, the slab's its live tiles) leaves only the dead
        lanes of the last page or tile, booked as ``tile_pad`` so the
        ledger never silently credits zero; the gather and the
        rectangular window attend ``pages`` pages or ``span``
        positions for every slot."""
        from veles_tpu.parallel.decode import (
            page_overshoot_tokens, span_overshoot_tokens,
            tile_pad_tokens)
        if not self._has_rows:      # no window, nothing past its end
            return 0
        if self.paged_kernel:
            return tile_pad_tokens(lens, self.page_size, chunk)
        if self.paged:
            return page_overshoot_tokens(lens, pages, self.page_size,
                                         chunk)
        if slab_kernel:
            from veles_tpu.ops.slab_attention import TILE
            return tile_pad_tokens(lens, TILE, chunk)
        return span_overshoot_tokens(lens, span, chunk)

    def _active(self):
        active = numpy.zeros(self.slots, bool)
        for slot in self._slot_req:
            active[slot] = True
        return active

    def step(self):
        """Admit what fits, advance every active slot one token; returns
        {request_id: token} for the tokens generated this step."""
        from veles_tpu.parallel.decode import slot_step

        self._admit_pending()
        if not self._slot_req:
            return {}
        snapshot = dict(self._slot_req)
        scope_lens = [self._slot_len[s] for s in snapshot] \
            if self.scope.enabled else None
        span = pb = 0
        t0 = time.perf_counter()
        if self.paged:
            from veles_tpu.parallel.kv_pool import paged_slot_step
            step = (self._aot.paged_step if self._aot is not None
                    else self._paged_fns[3] if self._paged_fns
                    else paged_slot_step)
            table = self._page_table_array(1)
            pb = int(table.shape[1])
            self.state, emitted = step(
                self.params, self.embed_table, self.heads, self.state,
                table, jnp.asarray(self._active()),
                jnp.float32(self.temperature or 1.0),
                sample=bool(self.temperature), top_k=self.top_k)
        else:
            step = (self._aot.step if self._aot is not None
                    else slot_step)
            span = self._attended_span(1)
            self.state, emitted = step(
                self.params, self.embed_table, self.heads, self.state,
                jnp.asarray(self._active()),
                jnp.float32(self.temperature or 1.0),
                sample=bool(self.temperature), top_k=self.top_k,
                span=span)
        for slot in snapshot:
            self._slot_len[slot] += 1
        self.dispatch_counts["step"] += 1
        self._book_moe_path(self.slots)
        slab_kernel = self._book_attend_path(1).get(
            "attend_path") == "kernel"
        self.flight.note("step", rids=list(snapshot.values()))
        ledger_aot = None
        if self.ledger is not None:
            ledger_aot = self._dispatch_attribution(
                step, "paged.step" if self.paged else "decode.step")[1]
        emitted = numpy.asarray(emitted)
        if self.scope.enabled:
            # the step path syncs inline, so the whole call is one
            # decode-compute window; every active lane keeps its token
            overshoot = self._attend_overshoot(scope_lens, 1, span, pb,
                                               slab_kernel)
            elapsed = time.perf_counter() - t0
            self.scope.note_dispatch(1, self.slots, len(snapshot),
                                     overshoot, elapsed,
                                     paged=self.paged, span=span,
                                     pages=pb,
                                     kernel=self.paged_kernel
                                     or slab_kernel)
            self.scope.note_collect(len(snapshot), len(snapshot), 0.0)
        out = {}
        for slot, rid in snapshot.items():
            token = int(emitted[slot])
            if not self.results[rid]:
                self.scope.note_slot_first(rid)
            self.results[rid].append(token)
            out[rid] = token
            if ledger_aot is not None:
                self.ledger.note_tokens(self._ledger_rows.get(rid),
                                        1, aot=ledger_aot)
            self.tokens_out += 1
            self._budget[rid] -= 1
            done = self._budget[rid] <= 0 or (
                self.eos is not None and token == self.eos)
            if done:
                del self._slot_req[slot]
                del self._budget[rid]
                self.admitted_at.pop(rid, None)
                self._ledger_rows.pop(rid, None)
                self._retire_trace(rid)
                self._free.append(slot)
                self._release_slot_pages(slot)
                self.scope.note_slot_retire(rid)
        self.steps += 1
        return out

    def step_many(self, n):
        """``n`` decode steps as ONE device dispatch (throughput mode
        for high-RTT hosts — one round trip per ``n`` tokens).
        Admission happens before the chunk; a request finishing
        mid-chunk has its tail tokens discarded and its slot recycles
        at the chunk boundary. Returns {request_id: [tokens...]}."""
        dispatched = self.dispatch_chunk(n)
        if dispatched is None:
            return {}
        return self.collect_chunk(dispatched)

    def collect_chunk(self, dispatched):
        """Materialize one dispatched chunk (this is the device sync)
        and account its tokens against the requests that were assigned
        when it was DISPATCHED. Requests that finished or were
        cancelled while the chunk was in flight (pipelined mode keeps
        their slot active one extra chunk) are skipped; tail tokens
        past a budget or eos are discarded."""
        emitted, snapshot, dispatch_info = (
            dispatched if len(dispatched) == 3
            else (dispatched[0], dispatched[1], None))
        # span writes stay outside the timed window (see decode.admit)
        from veles_tpu.parallel.decode import split_emitted

        emitted, load = split_emitted(emitted)
        with self._span("decode.collect",
                        list(snapshot.values())) as span:
            t0 = time.perf_counter()
            emitted = numpy.asarray(emitted)  # (chunk, slots) — syncs
            elapsed = time.perf_counter() - t0
            if load is not None:
                span.annotate(**self._book_moe_load(numpy.asarray(load),
                                                    len(snapshot)))
        self.metrics.observe(
            "veles_decode_collect_seconds", elapsed,
            buckets=DECODE_BUCKETS,
            help="chunk readback (device sync) time")
        self.flight.note("collect", chunk=int(emitted.shape[0]),
                         ms=round(elapsed * 1000, 3))
        # online MFU (observe/xla_stats.py): wall time between chunk
        # completions is the steady-state per-chunk step time under the
        # lag-1 pipeline (the device computes continuously); the
        # tracker divides the chunk program's cost_analysis FLOPs by
        # this cadence for the veles_mfu_ratio gauge
        if self._xla.enabled:
            done = time.monotonic()
            if self._last_chunk_done is not None:
                self._xla.observe_step(
                    "paged.dispatch" if self.paged
                    else "decode.dispatch",
                    done - self._last_chunk_done)
            self._last_chunk_done = done
        if self.dispatch_log is not None:
            self.dispatch_log.append(("collect", emitted.shape[0]))
        out = {}
        kept_total = 0
        for slot, rid in snapshot.items():
            if rid not in self._budget:
                continue  # retired while this chunk was in flight
            stream = emitted[:, slot].tolist()
            keep = min(self._budget[rid], len(stream))
            tokens = stream[:keep]
            if self.eos is not None and self.eos in tokens:
                tokens = tokens[:tokens.index(self.eos) + 1]
            kept_total += len(tokens)
            if tokens and not self.results[rid]:
                self.scope.note_slot_first(rid)
            self.results[rid].extend(tokens)
            out[rid] = tokens
            if self.ledger is not None and tokens:
                # the request-truth cadence: one stamp per collected
                # chunk per request, with the DISPATCHING program's
                # aot/live attribution captured at dispatch time
                self.ledger.note_tokens(
                    self._ledger_rows.get(rid), len(tokens),
                    aot=bool(dispatch_info and dispatch_info.get("aot")))
            self.tokens_out += len(tokens)
            self._budget[rid] -= len(tokens)
            done = self._budget[rid] <= 0 or (
                self.eos is not None and tokens
                and tokens[-1] == self.eos)
            if done:
                del self._budget[rid]
                self.admitted_at.pop(rid, None)
                self._ledger_rows.pop(rid, None)
                self._retire_trace(rid)
                self.scope.note_slot_retire(rid)
                if self._slot_req.get(slot) == rid:
                    del self._slot_req[slot]
                    self._free.append(slot)
                    self._release_slot_pages(slot)
        if self.scope.enabled:
            # live lane-steps dispatched vs tokens actually delivered:
            # the gap is the lag-1 retirement tails, budget clamps and
            # post-eos positions — cause "discard"
            self.scope.note_collect(
                len(snapshot) * int(emitted.shape[0]), kept_total,
                elapsed)
        return out

    def _book_moe_load(self, load, lanes):
        """Book one chunk's expert load ``(steps, expert blocks,
        experts)``, run with ``lanes`` live slots; returns what the
        chunk's collect span says of it."""
        books = self.moe_load
        per_expert = load.sum(0, dtype=numpy.int64)
        books["assignments"] = per_expert if books["assignments"] is None \
            else books["assignments"] + per_expert
        said = {"moe_assignments": int(per_expert.sum()),
                "moe_experts_touched": int((load > 0).sum())}
        row = books["by_lanes"].setdefault(lanes, [0, 0, 0])
        row[0] += load.shape[0] * load.shape[1]
        row[1] += said["moe_assignments"]
        row[2] += said["moe_experts_touched"]
        labels = {"lanes": str(lanes)}
        self.metrics.incr(
            "veles_moe_assignments_total", said["moe_assignments"],
            labels=labels, help="(token, expert) assignments the decode "
            "steps routed, by live slots of the chunk")
        self.metrics.incr(
            "veles_moe_experts_touched_total", said["moe_experts_touched"],
            labels=labels, help="experts with at least one assignment, "
            "summed over decode steps and expert blocks")
        self.metrics.set(
            "veles_moe_load_max_over_mean", self.moe_load_max_over_mean(),
            help="the busiest expert's assignments over the mean")
        return said

    def _book_moe_path(self, tokens):
        """Book one dispatch whose feed-forward sees ``tokens`` tokens
        at once by the tiling its grouped products take and the slices
        its kernel takes an expert in; returns what the dispatch's span
        says of them (nothing for a model without routed experts)."""
        if self.moe_load is None:
            return {}
        from veles_tpu.parallel.blocks import expert_plan

        path, slices = expert_plan(self.params, tokens)
        self.moe_load["paths"][path] += 1
        self.moe_load["slices"][slices] = \
            self.moe_load["slices"].get(slices, 0) + 1
        self.metrics.incr(
            "veles_moe_expert_dispatches_total", 1,
            labels={"path": path}, help="dispatches (chunks, steps, "
            "admissions) by the tiling of the routed experts' products")
        return {"moe_expert_path": path, "moe_expert_slices": slices}

    def _book_prompt_path(self, rows, bucket, lens):
        """Book one admission whose blocks take ``rows`` prompts of
        ``bucket`` positions at once, ``lens`` the live prompts' own
        lengths: how its grouped blocks attend the prompts and how many
        of them pass a window block's window; returns what the
        admission's span says of it (nothing for a model without
        such blocks)."""
        if self.prompt_paths is None:
            return {}
        from veles_tpu.parallel.blocks import arch_of, prompt_attend_path

        path = prompt_attend_path(self.params, rows, bucket, self.heads)
        self.prompt_paths[path] += 1
        window = arch_of(self.params).window
        past = sum(1 for n in lens if window and n > window)
        self.admits_past_window += past
        return {"prompt_attend_path": path, "rows_past_window": past}

    def _book_attend_path(self, n):
        """Book one decode dispatch of ``n`` steps of the dense slab
        by how its program attends the cache and writes the chunk's
        blocks to it; returns what the dispatch's span says of it
        (nothing for the page pool)."""
        if self.attend_paths is None:
            return {}
        from veles_tpu.parallel.decode import (slot_attend_path,
                                               slot_write_path)

        path = slot_attend_path(self.params, self.state)
        self.attend_paths[path] += 1
        self.metrics.incr(
            "veles_decode_attend_dispatches_total", 1,
            labels={"path": path}, help="decode dispatches of the dense "
            "slab (chunks, steps) by how the program attends the cache")
        said = {"attend_path": path}
        if self.slot_holds.get("slot_ring_bytes"):
            # the window blocks' rings are attended as the rest is
            said["window_path"] = path
        if self.write_paths is not None:
            said["block_write_path"] = slot_write_path(self.state, n)
            self.write_paths[said["block_write_path"]] += 1
        if self.state_paths is not None:
            said["state_path"] = self._state_path()
            self.state_paths[said["state_path"]] += 1
            self.metrics.incr(
                "veles_decode_state_dispatches_total", 1,
                labels={"path": said["state_path"]},
                help="decode dispatches (chunks, steps) by how the "
                "program takes the retention blocks' state through "
                "the chip")
        return said

    def _state_path(self):
        """``kernel`` or ``xla``: how this decoder's step programs
        take the retention blocks' fixed state through the chip."""
        from veles_tpu.parallel.decode import slot_state_path

        return slot_state_path(self.params, self.state)

    def moe_load_max_over_mean(self):
        """The busiest expert's assignments over the mean expert's, of
        all the decode steps so far (the worst expert block's); None
        before any."""
        total = None if self.moe_load is None \
            else self.moe_load["assignments"]
        if total is None or not total.sum():
            return None
        return float((total.max(-1) / total.mean(-1)).max())

    def moe_counters(self):
        """The experts' books as ``/healthz`` has them among its
        counters."""
        return {"moe_load_max_over_mean": self.moe_load_max_over_mean(),
                "moe_by_lanes": {
                    str(lanes): list(row) for lanes, row
                    in sorted(self.moe_load["by_lanes"].items())},
                "moe_expert_path": dict(self.moe_load["paths"]),
                "moe_expert_slices": {
                    str(n): count for n, count
                    in sorted(self.moe_load["slices"].items())}}

    def dispatch_chunk(self, chunk):
        """Admit what fits and enqueue one chunk WITHOUT waiting for
        it; returns an opaque handle for :meth:`collect_chunk` (or
        None when nothing is active). The handle holds the
        un-materialized emitted tokens + the slot assignment at
        dispatch time; the pipelined driver dispatches chunk N+1
        before collecting chunk N so the readback hides behind device
        compute."""
        from veles_tpu.parallel.decode import slot_step_many

        self._admit_pending()
        if not self._slot_req:
            return None
        snapshot = dict(self._slot_req)
        scope_lens = [self._slot_len[s] for s in snapshot] \
            if self.scope.enabled else None
        span = pb = 0
        said = {}
        if self.kv_layout is not None and not self._layout_said \
                and self._tracer.enabled:
            # the first dispatch span of a traced run says which
            # layout the run is in
            self._layout_said = True
            said = {"kv_layout": json.dumps(self.kv_layout,
                                            sort_keys=True)}
        said.update(self._book_moe_path(self.slots))
        said.update(self._book_attend_path(chunk))
        slab_kernel = said.get("attend_path") == "kernel"
        # span writes stay outside the timed window (see decode.admit)
        with self._span("paged.dispatch" if self.paged
                        else "decode.dispatch",
                        list(snapshot.values()), chunk=chunk, **said):
            t0 = time.perf_counter()
            if self.paged:
                from veles_tpu.parallel.kv_pool import \
                    paged_slot_step_many
                step_many = (self._aot.paged_step_many
                             if self._aot is not None
                             else self._paged_fns[4] if self._paged_fns
                             else paged_slot_step_many)
                table = self._page_table_array(chunk)
                pb = int(table.shape[1])
                self.state, emitted = step_many(
                    self.params, self.embed_table, self.heads,
                    self.state, table,
                    jnp.asarray(self._active()), chunk,
                    jnp.float32(self.temperature or 1.0),
                    sample=bool(self.temperature), top_k=self.top_k)
            else:
                step_many = (self._aot.step_many
                             if self._aot is not None
                             else slot_step_many)
                span = self._attended_span(chunk)
                self.state, emitted = step_many(
                    self.params, self.embed_table, self.heads,
                    self.state, jnp.asarray(self._active()), chunk,
                    jnp.float32(self.temperature or 1.0),
                    sample=bool(self.temperature), top_k=self.top_k,
                    span=span)
            elapsed = time.perf_counter() - t0
        if self.scope.enabled:
            overshoot = self._attend_overshoot(scope_lens, chunk, span,
                                               pb, slab_kernel)
            self.scope.note_dispatch(chunk, self.slots, len(snapshot),
                                     overshoot, elapsed,
                                     paged=self.paged, span=span,
                                     pages=pb,
                                     kernel=self.paged_kernel
                                     or slab_kernel)
        self.metrics.observe(
            "veles_decode_dispatch_seconds", elapsed,
            buckets=DECODE_BUCKETS,
            help="chunk enqueue (host-blocking dispatch) time")
        # mirror the device-side length advance (active lanes advance
        # every step of the chunk, even past retirement — the span for
        # the NEXT dispatch only consults live slots)
        for slot in snapshot:
            self._slot_len[slot] += chunk
        self.dispatch_counts["chunk"] += 1
        self.flight.note("dispatch", chunk=chunk,
                         rids=list(snapshot.values()),
                         ms=round(elapsed * 1000, 3))
        if self.dispatch_log is not None:
            self.dispatch_log.append(("dispatch", chunk))
        self.steps += chunk
        dispatch_info = None
        if self.ledger is not None:
            program, aot_served = self._dispatch_attribution(
                step_many,
                "paged.dispatch" if self.paged else "decode.dispatch")
            dispatch_info = {"program": program, "aot": aot_served,
                             "chunk": chunk}
        return emitted, snapshot, dispatch_info

    def drain_pipelined(self, chunk, max_steps=100000, admit=None):
        """Throughput drain: chunk N's tokens are read back while chunk
        N+1 is already computing, so the host round trip hides behind
        device compute.
        Retirement and admission decisions lag one chunk — a finished
        slot decodes one extra chunk whose tokens are discarded (its
        cache lane is fully overwritten on the next admit), which is
        the price of keeping the device queue fed. Token streams are
        identical to the unpipelined drain. ``admit`` is an optional
        zero-arg callable invoked once per pass — the caller's
        staggered-submission hook (requests joining mid-flight)."""
        pending = None
        for _ in range(max_steps):
            if admit is not None:
                admit()
            current = self.dispatch_chunk(chunk)
            if pending is not None:
                self.collect_chunk(pending)
            pending = current
            if pending is None:
                if not self.busy:
                    return self.results
                # nothing active but requests queued (all slots were
                # busy at dispatch time): loop admits them next pass
        raise RuntimeError("decoder did not drain in %d steps"
                           % max_steps)

    def run_until_drained(self, max_steps=100000, chunk=1,
                          before_step=None):
        """Drive the decoder until every submitted request finished
        (``chunk`` > 1 uses :meth:`step_many` between admissions).
        ``before_step`` is called once per device dispatch (the chaos
        hook's seat); the ``max_steps`` budget bounds the loop, so a
        decoder that stops producing progress raises instead of
        spinning forever."""
        for _ in range(max_steps):
            if not self.busy:
                return self.results
            if before_step is not None:
                before_step()
            if chunk > 1:
                self.step_many(chunk)
            else:
                self.step()
        raise RuntimeError("decoder did not drain in %d steps"
                           % max_steps)


def _non_finite_leaf(tree):
    """The keypath of the first floating weight leaf containing a
    non-finite value, or None when clean — the deploy gate's
    poisoned-checkpoint check (docs/zero_downtime.md). Evaluated
    device-side per leaf (one scalar readback each), so a sharded
    checkpoint is never gathered to the host. Integer leaves (int8
    tier payloads) cannot hold NaN and are skipped."""
    import jax
    import jax.numpy as jnp

    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        dtype = getattr(leaf, "dtype", None)
        # issubdtype, not numpy kind: bfloat16 registers as a custom
        # (void-kind) numpy dtype but is a jnp.floating subtype
        if dtype is None or not jnp.issubdtype(dtype, jnp.floating):
            continue
        if not bool(jnp.isfinite(jnp.asarray(leaf)).all()):
            return jax.tree_util.keystr(path)
    return None


class GenerateAPI:
    """HTTP front for :class:`ContinuousDecoder` — the LLM analogue of
    :class:`RESTfulAPI` (which serves per-tick forward passes, the
    reference surface). ``POST <path>`` with
    ``{"tokens": [...], "n_tokens": N?}`` answers
    ``{"tokens": [...]}`` once the request's stream completes.

    Handler threads only stage requests and block on a per-request
    event; ONE driver thread owns the decoder (it is not thread-safe)
    — admitting staged prompts and running lag-1 double-buffered chunk
    dispatches (chunk N+1 enqueues before chunk N's readback — see
    :meth:`_drive` and docs/serving_performance.md) while anything is
    in flight, so concurrent requests batch into the slot pool
    automatically, new ones join mid-flight, and the device queue
    stays fed through the host round trip. ``/healthz`` reports
    rolling p50/p95 time-to-first-token and queue-wait.

    Survival layer (docs/serving_robustness.md): admission is bounded
    by ``max_queue`` (429 + ``Retry-After`` beyond it, 503 while not
    ready); every request carries a deadline (``deadline`` default,
    per-request ``"deadline_s"`` override) and an expired request is
    cancelled INSIDE the decoder — slot freed, results reaped — instead
    of burning a slot for its full budget; and a decoder failure trips
    a circuit breaker that sheds in-flight requests, rebuilds the
    decoder from the held params/embed_table with exponential backoff,
    probes it with a real decode, and closes again. ``/healthz`` and
    ``/readyz`` expose the breaker state and the trip/rebuild/shed/
    expired counters. ``chaos`` accepts a
    :class:`veles_tpu.serving_chaos.ServingChaosMonkey` (default: built
    from ``root.common.serve.chaos``).

    Closed loop (observe/governor.py, docs/serving_robustness.md):
    ``governor`` accepts a :class:`ServingGovernor` (default: built
    from ``root.common.serve.governor`` / ``--serve-governor``; None
    without config). The governor ticks on THIS driver thread and acts
    through four seams — :meth:`request_tier` (graceful demote/promote
    down the bf16→int8→int8-kv ladder on SLO burn),
    :attr:`effective_max_queue` + ``ServingHealth.retry_after_s``
    (admission resize and Retry-After priced from the pool release
    rate), AOT bucket prewarm, and :meth:`request_trip` (proactive
    breaker guard on recompile storms / memory pressure). Every
    actuation lands in the flight ring, the ``veles_governor_*``
    metrics and — for demotions — on the request ledger rows."""

    #: extra handler-side wait beyond the request deadline before the
    #: handler gives up on the driver (wedged-driver backstop)
    BACKSTOP_GRACE = 10.0

    def __init__(self, params, embed_table, heads, slots=4,
                 max_len=512, n_tokens=32, temperature=0.0, top_k=0,
                 eos=None, key=None, port=0, host="127.0.0.1",
                 path="/generate", chunk=8, request_timeout=None,
                 max_queue=None, deadline=None, rebuild_backoff=None,
                 rebuild_backoff_max=None, chaos=None, quantize=None,
                 tile=None, mesh=None, mesh_axis="model", paged=None,
                 page_size=None, pool_pages=None,
                 aot=None, slo=None, ledger=None, governor=None):
        import queue

        from veles_tpu.core.config import root

        serve_cfg = root.common.serve
        #: serving mesh (--serve-mesh / root.common.serve.mesh, or an
        #: explicit Mesh): the decoder this API drives — and every
        #: decoder a breaker rebuild constructs — serves tensor-parallel
        #: over it (docs/sharded_serving.md). Built HERE (not in the
        #: decoder) so the rebuild path reuses one mesh object and its
        #: compiled-program cache entries. Raw attribute read, NOT
        #: serve_cfg.get(): get() collapses Config SUBTREES to the
        #: default, which would silently ignore a dict-style
        #: ``root.common.serve.mesh.model = 8`` config.
        if mesh is None:
            try:
                mesh_spec = object.__getattribute__(serve_cfg, "mesh")
            except AttributeError:
                mesh_spec = None
            mesh = build_serve_mesh(mesh_spec)
        #: default per-request deadline (seconds); ``request_timeout``
        #: is the legacy name for the same knob. Validated BEFORE the
        #: (expensive) decoder build, so a server misconfiguration
        #: fails at startup — never as a 400 blaming a field the
        #: client didn't send.
        if deadline is None:
            deadline = (request_timeout if request_timeout is not None
                        else serve_cfg.get("deadline", 300.0))
        self.deadline = float(deadline)
        if not math.isfinite(self.deadline) \
                or not 0 < self.deadline <= 1e7:
            raise ValueError(
                "serve deadline (--serve-deadline / deadline=) must "
                "be a positive number of seconds (at most 1e7), "
                "got %r" % deadline)
        #: paged KV pool serving (docs/paged_kv.md): --serve-paged /
        #: root.common.serve.paged turns the dense slot slab into a
        #: page pool with shared-prefix admission; --serve-page-size /
        #: --serve-pool-pages size it. Resolved HERE so the breaker's
        #: rebuild path reconstructs the same tier.
        if paged is None:
            paged = bool(serve_cfg.get("paged", False))
        if page_size is None:
            page_size = serve_cfg.get("page_size", None)
        if pool_pages is None:
            pool_pages = serve_cfg.get("pool_pages", None)
        #: AOT compiled-program boot (--serve-aot PATH /
        #: root.common.serve.aot — docs/aot_artifacts.md): load the
        #: bundle ONCE here, so the decoder and every breaker-rebuild
        #: decoder reuse the same compiled programs (a trip never pays
        #: a second deserialize+compile). Strict gating: a stale bundle
        #: (schema / jax / jaxlib / fingerprint / mesh) is refused with
        #: the stale field named, and serving proceeds on live
        #: compilation — never a wrong-answer execute.
        if aot is None:
            aot_path = serve_cfg.get("aot", None)
            if aot_path:
                from veles_tpu.aot.loader import (AotCompatError,
                                                  load_bundle)
                try:
                    aot = load_bundle(aot_path, mesh=mesh)
                except (AotCompatError, ValueError, OSError) as exc:
                    import logging
                    logging.getLogger("GenerateAPI").warning(
                        "AOT bundle %s refused (%s): %s — serving "
                        "boots with live compilation instead",
                        aot_path,
                        getattr(exc, "field", "unreadable"), exc)
                    aot = None
        if aot is not None and aot.chunk is not None \
                and int(aot.chunk) != int(chunk):
            # not a refusal — step programs still serve — but the
            # dominant per-token dispatch program would miss on every
            # span and live-compile silently, which defeats the boot
            import logging
            logging.getLogger("GenerateAPI").warning(
                "AOT bundle was built for dispatch chunk %d but this "
                "server drives chunk %d: every chunked dispatch will "
                "fall back to live compilation (veles_aot_misses_"
                "total) — rebuild with --chunk %d or pass chunk=%d",
                aot.chunk, chunk, chunk, aot.chunk)
        #: request-truth plane (observe/reqledger.py): every request
        #: this API serves gets a ledger row with its full stage
        #: waterfall; the PROCESS ledger by default so /debug/requests,
        #: the autopsy CLI and flight-recorder dumps see one view.
        #: Threaded into the decoder (and every breaker-rebuild
        #: decoder, via _decoder_kwargs) for the dispatch-time hooks.
        self.ledger = ledger if ledger is not None \
            else get_request_ledger()
        #: SLO engine (observe/slo.py): root.common.observe.slo /
        #: --serve-slo objectives over multi-window rolling buckets;
        #: None without config — the ledger path stays lock-free
        self.slo = slo if slo is not None else get_slo_engine()
        self._decoder_kwargs = dict(
            params=params, embed_table=embed_table, heads=heads,
            slots=slots, max_len=max_len, n_tokens=n_tokens,
            temperature=temperature, top_k=top_k, eos=eos, key=key,
            quantize=quantize, tile=tile, mesh=mesh,
            mesh_axis=mesh_axis, paged=bool(paged),
            page_size=page_size, pool_pages=pool_pages, aot=aot,
            ledger=self.ledger)
        self.decoder = ContinuousDecoder(**self._decoder_kwargs)
        self.vocab = embed_table.shape[0]
        self.port = port
        self.host = host
        self.path = path
        self.chunk = chunk
        #: staged + in-flight bound; beyond it new arrivals are shed
        #: with 429 + Retry-After instead of queueing unboundedly
        #: (<= 0 explicitly DISABLES the bound — load shedding off)
        self.max_queue = int(max_queue if max_queue is not None
                             else serve_cfg.get("max_queue", 64))
        self.rebuild_backoff = float(
            rebuild_backoff if rebuild_backoff is not None
            else serve_cfg.get("rebuild_backoff", 0.5))
        self.rebuild_backoff_max = float(
            rebuild_backoff_max if rebuild_backoff_max is not None
            else serve_cfg.get("rebuild_backoff_max", 30.0))
        if chaos is None:
            from veles_tpu.serving_chaos import ServingChaosMonkey
            chaos = ServingChaosMonkey.from_config()
        self.chaos = chaos
        self.health = ServingHealth(name="generate-api")
        if self.decoder.pool is not None:
            self.health.attach_pool(self.decoder.pool)
        if self.slo is not None:
            self.health.attach_slo(self.slo)
        #: the serving goodput observatory (observe/servescope.py):
        #: the decoder feeds the process scope per dispatch; the
        #: driver books queue-empty idle and runs the waste/occupancy
        #: autopsy OFF the record path; /healthz and the web-status
        #: cell mirror its occupancy/goodput summary
        self.scope = get_serve_scope()
        self.health.attach_servescope(self.scope)
        # deploy state on /healthz: the weight version stamp and a
        # live rollout's snapshot (docs/zero_downtime.md)
        self.health.attach_deploy(self)
        #: closed-loop governor (observe/governor.py,
        #: root.common.serve.governor / --serve-governor): the control
        #: loop over the sensors above. None without config — the
        #: driver pays one attribute check per pass and every knob
        #: stays the static flag it was.
        self._base_tier = self.decoder.quantize or "bf16"
        if governor is None:
            from veles_tpu.observe.governor import ServingGovernor
            governor = ServingGovernor.from_config()
        self.governor = governor
        if governor is not None:
            governor.set_base_tier(self._base_tier)
            self.health.attach_governor(governor)
            # the metric flight recorder (observe/history.py): the
            # governor's burn/pressure sensing runs THROUGH it, so the
            # incident autopsy replays exactly the trend windows the
            # demote decisions read (no second bookkeeping path)
            from veles_tpu.observe.history import ensure_metric_history
            governor.attach_history(ensure_metric_history())
        #: the governor's graceful tier-swap request (driver-thread
        #: owned) and the backoff stamp a failed swap arms so a sick
        #: device cannot wedge the driver in swap-probe loops
        self._tier_request = None
        self._tier_block_until = 0.0
        #: the governor's proactive-trip request (actuator d)
        self._trip_request = None
        #: zero-downtime deploy plane (docs/zero_downtime.md): the
        #: pending request_swap() holder — driver-applied behind the
        #: SAME drain-then-swap seam as the tier request — the
        #: one-slot rollback stash (raw params of the version the
        #: last successful swap/promote replaced, for rollback_swap)
        #: and the serving version tag
        self._swap_request = None
        self._param_stash = None
        self.version = None
        #: blue-green rollout (veles_tpu/rollout.py): the staged
        #: begin_rollout() holder, the live rollout controller, and
        #: the green engine bundle {"decoder", "waiting", "pending",
        #: "params", "embed_table"} — all driver-thread owned
        self._rollout_request = None
        self._rollout = None
        self._green = None
        self._staged = queue.Queue()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._httpd = None
        self._driver = None
        self._tripped = None  # breaker-open reason (None = closed)
        #: the lag-1 pipeline's chunk in flight (dispatched, not yet
        #: collected); discarded — never collected — when the breaker
        #: trips or the server stops
        self._pending = None
        # the one-slot rollback stash is DELIBERATE retention of a
        # whole param tree — tag it (memscope's exempt owner) so the
        # lifecycle-edge diff never mistakes it for a leak, and
        # dashboards see what rollback readiness costs in bytes
        try:
            from veles_tpu.observe.memscope import (get_memscope,
                                                    pytree_nbytes)
            get_memscope().register(
                "param_stash", self,
                lambda api: (pytree_nbytes(api._param_stash[0])
                             + pytree_nbytes(api._param_stash[1])
                             if api._param_stash is not None else 0))
        except Exception:
            pass

    # -- driver thread (sole owner of the decoder) ------------------------
    def _resolve(self, holder, outcome, **fields):
        """Resolve one admitted request exactly once: stamp the reply
        fields, book it out of the in-flight gauge under ``outcome``,
        wake its handler thread. Safe against the driver and a
        backstop-timing-out handler racing (dict.setdefault is atomic
        under the GIL; only the winner books the release)."""
        token = object()
        if holder.setdefault("resolved", token) is not token:
            return
        holder.update(fields)
        # release the request's admission-scratch tag (memscope
        # attribution) — exactly-once is inherited from the resolved
        # token; a single GIL-atomic dict pop either way
        from veles_tpu.observe.memscope import get_memscope
        get_memscope().scratch_drop(holder.pop("memscope_key", None))
        reserved = holder.pop("pool_reserved", 0)
        if reserved:
            pool = holder.get("pool")
            if pool is not None:
                pool.unreserve(reserved)
        self.health.release(outcome)
        row = holder.get("ledger_row")
        if row is not None:
            # close the request-truth row and feed the aggregate
            # planes from it: the SLO engine, the tpot health window,
            # and the exemplar-linked request histograms — once per
            # request, never on the token path
            self.ledger.resolve(row, outcome,
                                error=holder.get("error"))
            observe_request(row, engine=self.slo,
                            registry=get_metrics_registry(),
                            health=self.health)
        rollout = self._rollout
        if rollout is not None and "deploy" in holder:
            # the rollback predicate's per-role request feed (bounded
            # deque appends — safe from this thread or the handler's
            # backstop)
            rollout.note_resolved(holder["deploy"],
                                  outcome == "completed")
        holder["event"].set()

    def _drain_staged(self):
        import queue

        waiting = {}
        while True:
            try:
                prompt, budget, holder = self._staged.get_nowait()
            except queue.Empty:
                break
            # blue-green routing (veles_tpu/rollout.py): while a
            # rollout is live, the tenant's FIXED hash point against
            # the current fraction picks the engine — green tenants
            # submit into the candidate decoder and book into its own
            # waiting map, blue tenants stay on the primary path
            # byte-for-byte (the bit-identity contract)
            rollout = self._rollout
            green = self._green
            target, bucket, role = self.decoder, waiting, None
            if green is not None and rollout is not None:
                role = ("green" if rollout.routes_green(
                    holder.get("tenant") or "") else "blue")
                if role == "green":
                    target = green["decoder"]
                    bucket = green["waiting"]
            # the request may have been admitted (worst-case pages
            # reserved) against a PREVIOUS decoder's pool with a
            # breaker rebuild racing its staging: move the reservation
            # to the pool it will actually decode on. The pop is the
            # CLAIM — _resolve pops the same key, so exactly one side
            # ever releases (a handler-backstop timeout firing during
            # the move must not double-unreserve or strand pages on
            # the fresh pool).
            reserved = holder.pop("pool_reserved", 0)
            if reserved:
                pool = target.pool
                if pool is not None and holder.get("pool") is not pool:
                    holder["pool"].unreserve(reserved)
                    if pool.try_reserve(reserved):
                        holder["pool"] = pool
                    else:
                        # the fresh pool is already promised to
                        # capacity (a straggler staged across the trip
                        # while new admissions filled it): shed
                        # retryable like any other trip casualty — an
                        # unconditional reserve here would overcommit
                        # past capacity and break the no-deadlock
                        # invariant for EVERY admitted request
                        self._resolve(
                            holder, "shed",
                            error="rebuild raced admission: page "
                            "reservation lost; retry", code=503)
                        continue
                holder["pool_reserved"] = reserved
                if "resolved" in holder \
                        and holder.pop("pool_reserved", 0):
                    # _resolve ran between the claim and the give-back
                    # and found nothing to release — release here
                    holder["pool"].unreserve(reserved)
            try:
                rid = target.submit(prompt, budget,
                                    trace=holder.get("trace"))
            except ValueError as exc:
                # belt-and-braces: the handler pre-validated, but a
                # failed submit must never kill the driver thread —
                # resolve the request with the error instead
                self._resolve(holder, "errors", error=str(exc),
                              code=400)
                continue
            row = holder.get("ledger_row")
            if row is not None:
                # tier attribution is authoritative at SUBMIT time, on
                # the decoder that will actually serve the request: a
                # request staged while a tier swap was pending carries
                # the handler's pre-swap snapshot — re-stamp it here so
                # every demoted request's row truthfully names its tier
                # (and a promote-raced row drops back to the base tier)
                served_tier = target.quantize or "bf16"
                row["quant"] = served_tier
                if served_tier != self._base_tier:
                    if row.get("tier") != served_tier:
                        self.ledger.mark(row, "demoted",
                                         tier=served_tier)
                elif row.get("tier"):
                    row["tier"] = served_tier
            if role is not None:
                # deploy attribution: the role feeds the per-version
                # SLO slices (observe_request -> slo.record) and the
                # rollback predicate; the version names the weights
                holder["deploy"] = role
                if row is not None:
                    row["deploy"] = role
                    row["version"] = (rollout.version
                                      if role == "green"
                                      else self.version or "blue")
            target.ledger_link(rid, row)
            get_tracer().event("serve.submit",
                               parent=holder.get("trace"), rid=rid)
            bucket[rid] = holder
        return waiting

    def _fail_all(self, waiting, message, outcome="errors", code=503):
        """Resolve every in-flight and staged request with an error —
        nobody may be left blocking out their full deadline."""
        import queue

        for holder in waiting.values():
            self._resolve(holder, outcome, error=message, code=code)
        waiting.clear()
        while True:
            try:
                _, _, holder = self._staged.get_nowait()
            except queue.Empty:
                return
            self._resolve(holder, outcome, error=message, code=code)

    def _expire_deadlines(self, waiting, decoder=None):
        """Cancel every request whose deadline passed: the decoder slot
        frees immediately, the results entry is reaped, the client gets
        a 504 — a timed-out handler no longer leaks either.
        ``decoder`` defaults to the primary engine; a rollout's green
        engine passes its own (each engine expires its own map)."""
        if decoder is None:
            decoder = self.decoder
        now = time.monotonic()
        for rid in [r for r, h in waiting.items()
                    if h.get("deadline") is not None
                    and now >= h["deadline"]]:
            holder = waiting.pop(rid)
            decoder.cancel(rid)
            get_tracer().event("serve.expire",
                               parent=holder.get("trace"), rid=rid)
            self._resolve(holder, "expired", error="deadline exceeded",
                          code=504)

    def _trip(self, exc, waiting):
        """Open the circuit: the decoder's donated state is unusable.
        Shed everyone now queued/in-flight — loudly, with a retryable
        503 — instead of wedging each behind its full deadline. The
        flight recorder dumps its black box FIRST, so the ring still
        holds the dispatch tail and spans that led here."""
        flight = get_flight_recorder()
        flight.note("breaker.trip", error=str(exc)[:500],
                    inflight=len(waiting))
        flight.dump("breaker_trip",
                    extra={"error": str(exc)[:2000],
                           "health": self.health.snapshot()})
        self.health.incr("trips")
        self.health.set_breaker("open")
        self.health.set_ready(False)
        # a pending graceful swap is moot: the rebuild below lands on
        # the governed tier directly (_governed_kwargs)
        self._tier_request = None
        # pending deploy operations resolve with the trip (their
        # callers must not block out the timeout), and a live rollout
        # aborts — the breaker rebuild only reconstructs the PRIMARY
        # engine, so green requests would otherwise starve
        for pending in (self._swap_request, self._rollout_request):
            if pending is not None:
                pending["error"] = "breaker tripped: %s" % exc
                pending["event"].set()
        self._swap_request = None
        self._rollout_request = None
        if self._green is not None:
            self._abort_green(
                "blue breaker tripped during rollout: %s" % exc)
        self._tripped = "decode driver failed: %s; rebuilding" % exc
        self._fail_all(waiting, self._tripped, outcome="shed", code=503)

    def _governed_kwargs(self):
        """The decoder construction kwargs at the tier the governor
        currently wants (the configured tier without one): a rebuild
        or tier swap lands directly on the governed rung instead of
        flapping through the base tier first."""
        kwargs = dict(self._decoder_kwargs)
        tier = (self.governor.tier_name() if self.governor is not None
                else self._base_tier)
        kwargs["quantize"] = None if tier == "bf16" else tier
        return kwargs, tier

    def _build_probed_decoder(self, kwargs):
        """THE build-and-probe discipline shared by the breaker
        rebuild and the governor's tier swap: construct the decoder,
        carry the request-id counter over (per-request sampling keys
        ``fold_in(base, rid)`` must never repeat), then prove the
        device path end to end with a probe decode through the
        decoder's own :meth:`ContinuousDecoder.run_until_drained` —
        bounded step budget, the DRIVER's chunk size (what live
        traffic runs is what closes the gate), the chaos hook in the
        loop. Raises on any failure, including a hung probe."""
        decoder = ContinuousDecoder(**kwargs)
        decoder._next_id = self.decoder._next_id
        probe = decoder.submit([0], 1)
        before = (self.chaos.before_step if self.chaos is not None
                  else None)
        decoder.run_until_drained(max_steps=8, chunk=self.chunk,
                                  before_step=before)
        if not decoder.done(probe):
            raise RuntimeError("probe decode did not finish")
        decoder.results.pop(probe, None)
        return decoder

    def _install_decoder(self, decoder):
        """Swap the probed decoder in and re-point the health
        surface's pool mirror at its fresh pool."""
        self.decoder = decoder
        if decoder.pool is not None:
            self.health.attach_pool(decoder.pool)

    def _rebuild(self):
        """Build a fresh decoder from the held params/embed_table and
        prove the device path end to end with a probe decode
        (:meth:`_build_probed_decoder`); only a probed decoder takes
        traffic again. Returns True on success. The whole seam is a
        memscope lifecycle edge: the per-owner diff across it names
        anything that survived the trip it should not have (the
        classic leak — the old pool outliving the rebuild)."""
        from veles_tpu.observe.memscope import get_memscope
        memscope = get_memscope()
        memscope.edge_begin("breaker_rebuild")
        try:
            kwargs, tier = self._governed_kwargs()
            same_tier = tier == (self.decoder.quantize or "bf16")
            if self.decoder.pool is not None and same_tier:
                # the prefix cache OUTLIVES the decoder: its entries
                # (tokens, logits, per-page payload shadows) restore
                # into the fresh pool by page copy, so a breaker trip
                # never costs a re-prefill of every cached prompt.
                # Shadows are captured HERE, from the dying decoder —
                # not per cold admission (cached pages are read-only,
                # so trip-time bytes equal publication-time bytes)
                try:
                    self.decoder.pool.capture_shadows(
                        self.decoder.state)
                except Exception:
                    # a sick device can refuse the D2H reads; entries
                    # left unshadowed are dropped by restore_entries
                    # (the fresh decoder cold-prefills them again)
                    # rather than failing the whole rebuild
                    import traceback
                    traceback.print_exc()
                kwargs["prefix_cache"] = self.decoder.pool.cache
            decoder = self._build_probed_decoder(kwargs)
        except Exception:
            import traceback
            traceback.print_exc()
            # close the edge either way: a failed rebuild retries and
            # re-opens its own edge; leaving one dangling would pair a
            # later end with a stale baseline
            memscope.edge_end("breaker_rebuild", gc_collect=True)
            return False
        self._install_decoder(decoder)
        # the old decoder was just unbound; this seam already pays
        # seconds of compile, so a GC pass before the diff is free —
        # any owner still grown across the edge is a real retention,
        # and the verdict artifact (cold path, not the token loop)
        # names it
        verdict = memscope.edge_end("breaker_rebuild", gc_collect=True)
        if verdict is not None and verdict["leak"]:
            memscope.flush_incidents()
        return True

    # -- governor actuation seams (driver thread) -------------------------
    @property
    def effective_max_queue(self):
        """The admission bound actually enforced: the governor's
        resized limit while one is in effect, else ``max_queue``."""
        governor = self.governor
        if governor is not None:
            # single read: the driver-thread tick rebinds admit_limit
            # concurrently, and a check-then-read pair could return a
            # None the None-check just ruled out (try_admit treats
            # None as UNBOUNDED — an admission-control bypass)
            override = governor.admit_limit
            if override is not None:
                return override
        return self.max_queue

    def request_tier(self, tier):
        """Governor actuator (a): ask the driver for a GRACEFUL swap
        to ``tier`` — stop admitting, drain the in-flight requests at
        their admitted tier (bit-identical tokens), then rebuild the
        decoder at the new tier behind a probe. Ignored while a failed
        swap's backoff is armed, and idempotent at the live tier."""
        if time.monotonic() < self._tier_block_until:
            return
        if self._green is not None or self._rollout_request is not None:
            # one deploy-plane operation at a time: a tier rebuild
            # would race the rollout's two-engine bookkeeping; the
            # governor simply re-requests after the rollout lands
            return
        if tier == (self.decoder.quantize or "bf16"):
            self._tier_request = None
            return
        self._tier_request = tier

    def request_trip(self, reason):
        """Governor actuator (d): trip the breaker proactively at the
        top of the next drive pass (shed retryably + rebuild behind
        the probe) — a predicted stall is handled like a real one."""
        self._trip_request = reason

    # -- zero-downtime deploy seams (docs/zero_downtime.md) ---------------
    def request_swap(self, new_params, new_embed_table=None,
                     version=None):
        """Stage a live weight hot-swap: the driver stops admitting,
        drains every in-flight request on the OLD weights (nobody is
        shed), then swaps + probes behind the breaker's
        drain-then-swap seam (:meth:`_apply_swap`). Returns the
        request holder — its ``event`` sets when the swap landed or
        was refused; ``error`` carries the refusal. Latest-wins: a
        newer request supersedes an unapplied one (which resolves
        with an error). Refused while a blue-green rollout is live —
        one deploy-plane operation at a time."""
        if self._green is not None or self._rollout_request is not None:
            holder = {"event": threading.Event(),
                      "error": "refused: a blue-green rollout is in "
                               "progress"}
            holder["event"].set()
            return holder
        holder = {"event": threading.Event(), "params": new_params,
                  "embed_table": new_embed_table, "version": version}
        previous, self._swap_request = self._swap_request, holder
        if previous is not None:
            previous["error"] = "superseded by a newer swap request"
            previous["event"].set()
        self._wake.set()
        return holder

    def swap_params(self, new_params, new_embed_table=None,
                    version=None, timeout=120.0):
        """Blocking :meth:`request_swap`: True when the new weights
        serve; raises RuntimeError with the refusal reason (the old
        weights still serving — a refused swap sheds nothing) or on
        timeout."""
        holder = self.request_swap(new_params, new_embed_table,
                                   version=version)
        if not holder["event"].wait(timeout):
            raise RuntimeError("weight swap timed out after %.0fs"
                               % timeout)
        if "error" in holder:
            raise RuntimeError(holder["error"])
        return True

    def rollback_swap(self, timeout=120.0):
        """Swap back to the version the last successful swap (or
        rollout promote) replaced — the operator's one-step undo,
        served from the one-slot stash through the same drain seam."""
        if self._param_stash is None:
            raise RuntimeError("nothing to roll back to")
        params, embed_table, version = self._param_stash
        return self.swap_params(params, embed_table, version=version,
                                timeout=timeout)

    def begin_rollout(self, new_params, new_embed_table=None,
                      version="green", config=None, timeout=120.0):
        """Start a blue-green rollout: build + probe a SECOND engine
        on the new weights, shift tenant slices onto it along the
        configured fraction ladder, and auto-roll back when the green
        slice's burn/ttft trend breaks from the blue baseline
        (veles_tpu/rollout.py). Blocks until the green engine passed
        (or refused) its probe; returns the
        :class:`~veles_tpu.rollout.BlueGreenRollout` controller."""
        if self._swap_request is not None:
            raise RuntimeError("refused: a weight hot-swap is pending")
        holder = {"event": threading.Event(), "params": new_params,
                  "embed_table": new_embed_table, "version": version,
                  "config": config}
        previous, self._rollout_request = self._rollout_request, holder
        if previous is not None:
            previous["error"] = "superseded by a newer rollout request"
            previous["event"].set()
        self._wake.set()
        if not holder["event"].wait(timeout):
            raise RuntimeError("rollout start timed out after %.0fs"
                               % timeout)
        if "error" in holder:
            raise RuntimeError(holder["error"])
        return holder["rollout"]

    def _apply_swap(self, holder):
        """The live weight hot-swap (driver thread; both engines
        idle): validate the checkpoint, swap behind the drain seam,
        probe the new weights end to end, and on ANY failure restore
        the old pair atomically from the one-slot stash. No request
        is shed on either path — the staged queue held while the
        swap was pending and drains into whichever weights won."""
        flight = get_flight_recorder()
        from veles_tpu.observe.memscope import get_memscope
        memscope = get_memscope()
        memscope.edge_begin("swap_params")
        new_params = holder["params"]
        new_table = holder.get("embed_table")
        if self.chaos is not None:
            new_params = self.chaos.maybe_poison_swap(new_params)
        old = None
        probe = None
        try:
            bad = _non_finite_leaf(new_params if new_table is None
                                   else (new_params, new_table))
            if bad is not None:
                raise ValueError("non-finite weights at %s — the "
                                 "checkpoint is poisoned" % bad)
            old = self.decoder.swap_params(new_params, new_table)
            probe = self.decoder.submit([0], 1)
            before = (self.chaos.before_step
                      if self.chaos is not None else None)
            self.decoder.run_until_drained(max_steps=8,
                                           chunk=self.chunk,
                                           before_step=before)
            if not self.decoder.done(probe):
                raise RuntimeError("probe decode did not finish")
            self.decoder.results.pop(probe, None)
            probe = None
        except Exception as exc:
            import traceback
            traceback.print_exc()
            if probe is not None:
                try:
                    self.decoder.cancel(probe)
                except Exception:
                    pass
            if old is not None:
                # the one-slot rollback: restore the old pair through
                # the same seam (an identity reshard — 0 bytes move)
                try:
                    self.decoder.swap_params(old[0], old[1])
                except Exception as restore_exc:
                    # old weights unrestorable on top of a failed
                    # swap: this device state is not trustworthy —
                    # trip and rebuild from the held raw params
                    self.request_trip("weight-swap rollback failed: %s"
                                      % restore_exc)
            self.health.incr("swap_failures")
            flight.note("deploy.swap_refused", error=str(exc)[:200],
                        version=str(holder.get("version")))
            try:
                from veles_tpu.rollout import note_swap_failure
                note_swap_failure(str(exc),
                                  version=holder.get("version"))
            except Exception:
                import traceback
                traceback.print_exc()
            holder["error"] = ("swap refused, old weights serving: %s"
                               % exc)
            holder["event"].set()
            memscope.edge_end("swap_params", gc_collect=True)
            return False
        # success: the new checkpoint is authoritative for every
        # future breaker rebuild, and the replaced raw params become
        # the one-slot rollback stash
        self._param_stash = (self._decoder_kwargs["params"],
                             self._decoder_kwargs["embed_table"],
                             self.version)
        self._decoder_kwargs["params"] = holder["params"]
        if new_table is not None:
            self._decoder_kwargs["embed_table"] = new_table
        self.version = holder.get("version")
        self.decoder.version = self.version
        self.health.incr("param_swaps")
        flight.note("deploy.swap", version=str(self.version))
        holder["event"].set()
        # the one-slot rollback stash GROWS here by design — it
        # reports under the exempt "param_stash" owner, so the edge
        # diff only flags bytes nobody accounts for
        verdict = memscope.edge_end("swap_params", gc_collect=True)
        if verdict is not None and verdict["leak"]:
            memscope.flush_incidents()
        return True

    def _start_green(self, holder):
        """Build + probe the green engine for a blue-green rollout
        (driver thread). The green decoder shares the primary
        engine's AOT bundle, mesh and compiled-program caches but NOT
        its KV pool or prefix cache (old-weight KV must never serve
        green streams); its request ids sit 2^20 above blue's so
        ledger rows and slot timelines never collide."""
        from veles_tpu.rollout import BlueGreenRollout, RolloutConfig

        if self._green is not None:
            holder["error"] = "a rollout is already in progress"
            holder["event"].set()
            return
        kwargs = dict(self._decoder_kwargs)
        kwargs["params"] = holder["params"]
        if holder.get("embed_table") is not None:
            kwargs["embed_table"] = holder["embed_table"]
        try:
            bad = _non_finite_leaf((kwargs["params"],
                                    kwargs["embed_table"]))
            if bad is not None:
                raise ValueError("non-finite weights at %s — the "
                                 "checkpoint is poisoned" % bad)
            decoder = self._build_probed_decoder(kwargs)
        except Exception as exc:
            import traceback
            traceback.print_exc()
            self.health.incr("rollout_failures")
            get_flight_recorder().note("deploy.green_refused",
                                       error=str(exc)[:200])
            holder["error"] = "green build/probe refused: %s" % exc
            holder["event"].set()
            return
        decoder._next_id = self.decoder._next_id + (1 << 20)
        decoder.rollout_role = "green"
        decoder.version = holder.get("version") or "green"
        config = holder.get("config")
        if config is None:
            config = RolloutConfig.from_config()
        self._green = {"decoder": decoder, "waiting": {},
                       "pending": None, "params": holder["params"],
                       "embed_table": holder.get("embed_table")}
        self._rollout = BlueGreenRollout(decoder.version,
                                         config=config)
        self._rollout.start(api=self)
        self.health.incr("rollouts")
        holder["rollout"] = self._rollout
        holder["event"].set()

    def _abort_green(self, reason):
        """Tear the green engine down NOW (engine failure / blue
        breaker trip): green in-flight requests shed retryably — the
        zero-shed contract covers governed rollbacks, where green
        drains first; it cannot cover an engine that died — and the
        rollout lands in ``rolled_back`` with the reason."""
        green, self._green = self._green, None
        if green is None:
            return
        for holder in list(green["waiting"].values()):
            self._resolve(holder, "shed", error=str(reason), code=503)
        green["waiting"].clear()
        if self._rollout is not None:
            self._rollout.abort(reason, api=self)
        self.health.incr("rollout_aborts")
        get_flight_recorder().note("deploy.abort",
                                   reason=str(reason)[:200])

    def _rollout_step(self, waiting):
        """Drive the rollout's engine-surgery transitions (driver
        thread): finalize a rollback once green drained (zero shed —
        every green in-flight request finished first), and promote
        once the ladder reached full traffic and blue drained (the
        green decoder BECOMES the primary; the replaced weights go to
        the rollback stash)."""
        rollout, green = self._rollout, self._green
        if rollout is None or green is None:
            return
        gdec = green["decoder"]
        if rollout.state == "rolling_back":
            if not gdec.busy and green["pending"] is None \
                    and not green["waiting"]:
                self._green = None
                rollout.finish_rollback(api=self)
                self.health.incr("rollbacks")
            return
        if rollout.state == "promote_ready":
            if self.decoder.busy or self._pending is not None \
                    or waiting:
                return
            from veles_tpu.observe.memscope import get_memscope
            memscope = get_memscope()
            memscope.edge_begin("rollout_promote")
            self._param_stash = (self._decoder_kwargs["params"],
                                 self._decoder_kwargs["embed_table"],
                                 self.version)
            self._decoder_kwargs["params"] = green["params"]
            if green["embed_table"] is not None:
                self._decoder_kwargs["embed_table"] = \
                    green["embed_table"]
            gdec.rollout_role = None
            self._install_decoder(gdec)
            self.version = rollout.version
            # green's in-flight work rides over: its waiting map and
            # lag-1 pending chunk belong to the (new) primary now
            waiting.update(green["waiting"])
            self._pending = green["pending"]
            self._green = None
            rollout.finish_promote(api=self)
            self.health.incr("promotes")
            # the blue decoder was just unbound; the edge diff names
            # any owner it leaves behind (its pool must die with it)
            verdict = memscope.edge_end("rollout_promote",
                                        gc_collect=True)
            if verdict is not None and verdict["leak"]:
                memscope.flush_incidents()

    def _apply_tier(self, tier):
        """The graceful tier swap: the decoder is idle (the driver
        drained in-flight work first and held the staged queue), so
        nobody is shed — build the new-tier decoder, probe it, swap.
        The prefix cache does NOT carry across tiers (cached pages
        hold tier-specific KV bytes). A failed swap arms a backoff and
        leaves the live decoder serving. Returns True on success."""
        kwargs = dict(self._decoder_kwargs)
        kwargs["quantize"] = None if tier == "bf16" else tier
        try:
            decoder = self._build_probed_decoder(kwargs)
        except Exception:
            import traceback
            traceback.print_exc()
            self._tier_block_until = time.monotonic() \
                + 4 * self.rebuild_backoff
            get_flight_recorder().note("governor.tier_failed",
                                       tier=tier)
            return False
        self._install_decoder(decoder)
        self.health.incr("tier_swaps")
        get_flight_recorder().note("governor.tier", tier=tier,
                                   base=self._base_tier)
        return True

    def _note_progress(self, waiting, decoder=None):
        """Post-collect bookkeeping: record queue-wait (staged ->
        admitted into a slot) and time-to-first-token for the health
        window, and resolve every request whose stream completed.
        Runs once per drive pass per engine (``decoder`` defaults to
        the primary; the green engine passes its own)."""
        if decoder is None:
            decoder = self.decoder
        now = time.monotonic()
        for rid in list(waiting):
            holder = waiting[rid]
            staged_at = holder.get("staged_at")
            if "queue_waited" not in holder:
                admitted = decoder.admitted_at.get(rid)
                if admitted is not None:
                    holder["queue_waited"] = True
                    if staged_at is not None:
                        self.health.record_latency(
                            "queue_wait", max(0.0, admitted - staged_at))
            if "first_token" not in holder \
                    and decoder.results.get(rid):
                holder["first_token"] = True
                if staged_at is not None:
                    waited = max(0.0, now - staged_at)
                    self.health.record_latency("ttft", waited)
                    # per-role ttft feeds the rollout's green-vs-blue
                    # trend comparison (veles_tpu/rollout.py)
                    if self._rollout is not None \
                            and "deploy" in holder:
                        self._rollout.note_ttft(holder["deploy"],
                                                waited, now=now)
            if decoder.done(rid):
                tokens = decoder.results.pop(rid)
                get_tracer().event("serve.complete",
                                   parent=holder.get("trace"),
                                   rid=rid, tokens=len(tokens))
                self._resolve(waiting.pop(rid), "completed",
                              tokens=tokens)

    def _drive(self):
        """The lag-1 double-buffered live loop: each pass drains the
        staged queue, expires deadlines, DISPATCHES chunk N+1, and only
        then collects chunk N — the device computes the next chunk
        while the host reads the previous one back, admits, and
        resolves finished requests (the ``drain_pipelined`` recipe
        composed with deadlines, cancel, the breaker and the chaos
        hook). A chunk in flight when the breaker trips or the server
        stops is DISCARDED, never collected into shed requests'
        results; a request cancelled mid-chunk is skipped at collect
        (``collect_chunk`` consults the live budget map)."""
        waiting = {}
        backoff = self.rebuild_backoff
        tracer = get_tracer()
        try:
            while not self._stop.is_set():
                if self._tripped is not None:
                    # breaker open: drop the chunk in flight (its
                    # decoder state is unusable), shed stragglers fast,
                    # rebuild with exponential backoff, close only
                    # after the probe
                    self._pending = None
                    self._fail_all(waiting, self._tripped,
                                   outcome="shed", code=503)
                    if self._stop.wait(backoff):
                        break
                    if self._rebuild():
                        self._tripped = None
                        backoff = self.rebuild_backoff
                        self.health.incr("rebuilds")
                        self.health.set_breaker("closed")
                        self.health.set_ready(True)
                    else:
                        backoff = min(backoff * 2,
                                      self.rebuild_backoff_max)
                    continue
                # the pass's books, from a collect's end to the next
                # dispatch's start (with the block after the collect,
                # below): one span name, so that a device idle gap
                # under them reads as the scheduler's and not as
                # nobody's
                with tracer.span("serve.drive_books"):
                    if self.governor is not None:
                        # the closed loop rides the driver thread — one
                        # rate-limited pass, and a broken governor must
                        # never take the driver down with it
                        try:
                            self.governor.tick(self)
                        except Exception:
                            import traceback
                            traceback.print_exc()
                    if self._trip_request is not None:
                        # proactive breaker guard: treat the predicted
                        # stall exactly like a real one — shed retryably,
                        # rebuild behind the probe
                        reason = self._trip_request
                        self._trip_request = None
                        self._pending = None
                        self._trip(RuntimeError(reason), waiting)
                        continue
                    if self._rollout_request is not None:
                        holder = self._rollout_request
                        self._rollout_request = None
                        self._start_green(holder)
                    if self._tier_request is None \
                            and self._swap_request is None:
                        waiting.update(self._drain_staged())
                    # while a tier swap OR weight swap is pending the
                    # staged queue HOLDS: in-flight requests drain on the
                    # admitted tier/weights (the bit-identity contract),
                    # then the idle branch swaps and the next pass admits
                    # into the new decoder/weights
                    self._expire_deadlines(waiting)
                    green = self._green
                    if green is not None:
                        self._expire_deadlines(green["waiting"],
                                               decoder=green["decoder"])
                        # the rollout's control loop rides the driver
                        # thread like the governor's; a broken rollout
                        # must never take the driver down
                        if self._rollout is not None:
                            try:
                                self._rollout.tick(self)
                            except Exception:
                                import traceback
                                traceback.print_exc()
                        self._rollout_step(waiting)
                        green = self._green  # _rollout_step may clear it
                blue_idle = not self.decoder.busy \
                    and self._pending is None
                green_idle = green is None \
                    or (not green["decoder"].busy
                        and green["pending"] is None)
                if blue_idle and green_idle:
                    if self._tier_request is not None:
                        tier = self._tier_request
                        self._tier_request = None
                        if tier != (self.decoder.quantize or "bf16"):
                            self._apply_tier(tier)
                        continue
                    if self._swap_request is not None:
                        # both engines drained on the old weights (the
                        # staged queue held) — the hot-swap seam
                        holder = self._swap_request
                        self._swap_request = None
                        self._apply_swap(holder)
                        continue
                    # idle: the MFU cadence baseline must not span the
                    # gap, or the first chunk of the next burst feeds
                    # the whole idle wall time into the step-time EMA
                    self.decoder._last_chunk_done = None
                    idle_from = time.monotonic()
                    with tracer.span("serve.drive_idle"):
                        woke = self._wake.wait(timeout=0.05)
                    # queue-empty wall lands in the goodput
                    # decomposition as idle, not host
                    self.scope.note_idle(time.monotonic() - idle_from)
                    if woke:
                        self._wake.clear()
                    continue
                try:
                    if not blue_idle:
                        if self.chaos is not None:
                            self.chaos.before_step(self.decoder)
                        current = self.decoder.dispatch_chunk(self.chunk)
                        if self._pending is not None:
                            self.decoder.collect_chunk(self._pending)
                        self._pending = current
                    with tracer.span("serve.drive_books"):
                        if not blue_idle:
                            self._note_progress(waiting)
                        # the waste/occupancy autopsy (OFF the record
                        # path): trend series + detector-owned anomaly
                        # rules + a cooldown-limited incident naming
                        # the dominant waste cause; a broken autopsy
                        # must never take the driver down
                        try:
                            self.scope.autopsy_tick(get_metric_history())
                        except Exception:
                            import traceback
                            traceback.print_exc()
                except Exception as exc:  # device/runtime failure
                    import traceback
                    traceback.print_exc()
                    self._pending = None
                    self._trip(exc, waiting)
                    continue
                if green is not None and self._green is green:
                    # the green engine steps in the SAME drive pass
                    # (lag-1 on its own pending chunk); a green
                    # failure aborts the rollout, never the primary
                    try:
                        gdec = green["decoder"]
                        if not green_idle:
                            if self.chaos is not None:
                                self.chaos.before_step(gdec)
                            current = gdec.dispatch_chunk(self.chunk)
                            if green["pending"] is not None:
                                gdec.collect_chunk(green["pending"])
                            green["pending"] = current
                            self._note_progress(green["waiting"],
                                                decoder=gdec)
                    except Exception as exc:
                        import traceback
                        traceback.print_exc()
                        green["pending"] = None
                        self._abort_green("green engine failed: %s"
                                          % exc)
        finally:
            self._pending = None
            self._fail_all(waiting, "server stopped")
            green, self._green = self._green, None
            if green is not None:
                self._fail_all(green["waiting"], "server stopped")
            for attr in ("_swap_request", "_rollout_request"):
                holder = getattr(self, attr)
                setattr(self, attr, None)
                if holder is not None and not holder["event"].is_set():
                    holder["error"] = "server stopped"
                    holder["event"].set()

    # -- HTTP -------------------------------------------------------------
    def start(self):
        from http.server import BaseHTTPRequestHandler
        from veles_tpu.core.httpd import (BodyTooLarge, enable_metrics,
                                          QuietHandlerMixin, read_body,
                                          reply, retry_after_headers,
                                          serve_debug_history,
                                          serve_debug_index,
                                          serve_debug_memory,
                                          serve_debug_requests,
                                          serve_debug_serve,
                                          serve_health, serve_metrics,
                                          start_server)

        api = self
        # the deploy CLI's seam (deploy_cli.py): the newest started
        # surface is THE process's deploy target (weakly referenced —
        # a stopped/collected api drops out on its own)
        global _CURRENT_API
        import weakref
        _CURRENT_API = weakref.ref(self)
        # the telemetry plane (docs/observability.md): /metrics on this
        # surface exposes the health counters and the decoder's
        # dispatch/timing state via weakly-referenced scrape bridges
        # (api going away unregisters them) — the decoder is read
        # THROUGH api so a breaker rebuild swaps sources transparently
        registry = enable_metrics()
        bridge(registry, self.health, publish_serving_health)
        bridge(registry, self,
               lambda reg, live: publish_decoder(reg, live.decoder))
        # the request-truth ledger's own tallies (staged/resolved and
        # the trace-loss counters) are scrapeable beside the health
        # counters — observe/reqledger.py, docs/traffic_replay.md
        from veles_tpu.observe.reqledger import publish_request_ledger
        bridge(registry, self.ledger, publish_request_ledger)
        if self.slo is not None:
            # the SLO gauges ride every scrape of this surface AND the
            # fleet piggyback (registry.snapshot runs collectors)
            bridge(registry, self.slo,
                   lambda reg, live: live.publish(reg))
        if self.governor is not None:
            # governor actuations are ledger-visible on /metrics too:
            # tier level, effective limit, priced Retry-After and the
            # per-action actuation counters (observe/governor.py)
            from veles_tpu.observe.governor import publish_governor
            bridge(registry, self.governor, publish_governor)

        class Handler(QuietHandlerMixin, BaseHTTPRequestHandler):
            def do_GET(self):
                if serve_metrics(self):
                    return
                if serve_debug_requests(self, api.ledger):
                    return
                if serve_debug_history(self):
                    return
                if serve_debug_serve(self, api.scope, api.ledger):
                    return
                if serve_debug_memory(self):
                    return
                if serve_debug_index(self):
                    return
                if not serve_health(self, api.health):
                    self.send_error(404)

            def do_POST(self):
                if self.path.split("?")[0] != api.path:
                    self.send_error(404)
                    return
                try:
                    raw = read_body(self)
                except BodyTooLarge:
                    return  # 413 sent, nothing buffered
                try:
                    payload = json.loads(raw.decode())
                    tokens = payload["tokens"]
                    if not isinstance(tokens, list) or not tokens \
                            or not all(isinstance(t, int)
                                       and 0 <= t < api.vocab
                                       for t in tokens):
                        raise ValueError(
                            "tokens must be a non-empty list of ids "
                            "in [0, %d)" % api.vocab)
                    budget = payload.get("n_tokens")
                    if budget is not None and (
                            not isinstance(budget, int) or budget < 1):
                        raise ValueError("n_tokens must be a positive "
                                         "integer")
                    deadline_s = payload.get("deadline_s")
                    if deadline_s is None:
                        # server default, validated at construction
                        deadline_s = api.deadline
                    elif isinstance(deadline_s, bool) \
                            or not isinstance(deadline_s, (int, float)) \
                            or not math.isfinite(deadline_s) \
                            or not 0 < deadline_s <= 86400:
                        # finite + bounded: json accepts Infinity/NaN,
                        # and a huge value would overflow Event.wait()
                        raise ValueError("deadline_s must be a number "
                                         "of seconds in (0, 86400]")
                    prompt = numpy.asarray(tokens, numpy.int32)
                    # max_len / budget validation happens on the
                    # driver thread via submit(); pre-check here so
                    # the client gets a 400, not a timeout
                    limit = (budget if budget is not None
                             else api.decoder.n_tokens)
                    if len(prompt) + limit > api.decoder.max_len:
                        raise ValueError(
                            "prompt %d + n_tokens %d exceeds max_len "
                            "%d" % (len(prompt), limit,
                                    api.decoder.max_len))
                except (ValueError, TypeError, KeyError) as exc:
                    reply(self, {"error": str(exc)}, code=400)
                    return
                # trace context: continue the caller's trace from the
                # X-Veles-Trace header (or root a new one); the span
                # covers admission -> staged -> resolved, and its
                # context rides the holder so the driver/decoder spans
                # parent to it across threads
                parent = parse_trace_header(
                    self.headers.get(TRACE_HEADER))
                # multi-tenant attribution (the ROADMAP item-5
                # foundation): an optional client-supplied tenant id,
                # bounded, rides the ledger row and slices the SLO
                # gauges per tenant
                tenant = str(self.headers.get("X-Veles-Tenant")
                             or "").strip()[:64]
                # the handler waits on its event for the whole request,
                # so in a profiler capture its span would cover every
                # gap the driver leaves: it stays out of the capture
                with get_tracer().span("serve.request", parent=parent,
                                       capture=False) as req_span:
                    self._serve_admitted(prompt, budget, deadline_s,
                                         req_span, tenant,
                                         parent[0] if parent else None)

            def _serve_admitted(self, prompt, budget, deadline_s,
                                req_span, tenant="", trace_hint=None):
                # admission: atomic ready + queue-bound check; rejected
                # requests never stage, so the decoder queue is bounded.
                # The paged tier extends the decision to KV pages: the
                # request's WORST-CASE page demand is reserved under the
                # same lock (released when the request resolves), so an
                # admitted request can never deadlock waiting for pages
                # it was promised — a full pool 429s here instead, with
                # Retry-After priced from the observed page-release
                # rate (docs/paged_kv.md).
                # the request-truth row opens at staging (before the
                # admission verdict, so rejected requests leave a row
                # too); the driver/decoder hooks fill in the
                # waterfall. Trace identity: the server span's trace
                # when tracing is on, else the CLIENT's propagated id
                # — exemplars and autopsies link either way
                ctx = req_span.context()
                decoder = api.decoder
                row = api.ledger.stage(
                    api="generate-api",
                    trace=ctx[0] if ctx else trace_hint,
                    tenant=tenant,
                    prompt_len=len(prompt),
                    budget=(budget if budget is not None
                            else decoder.n_tokens),
                    bucket=decoder.bucket_for(len(prompt)),
                    quant=decoder.quantize,
                    breaker_gen=api.health.counter("rebuilds"),
                    deadline=deadline_s)
                serving_tier = decoder.quantize or "bf16"
                if serving_tier != api._base_tier:
                    # the governed tier in effect: the demoted
                    # request's row names its tier (the acceptance's
                    # ledger-visibility contract) beside the quant
                    # field that says what actually served it; the
                    # driver re-stamps both at submit time if a tier
                    # swap lands in between (_drain_staged)
                    api.ledger.mark(row, "demoted", tier=serving_tier)
                booked = {}
                pool_gate = None
                if api.decoder.pool is not None:
                    limit = (budget if budget is not None
                             else api.decoder.n_tokens)

                    def pool_gate():
                        # resolve the decoder INSIDE the gate (under
                        # the admission lock): a breaker rebuild swaps
                        # api.decoder concurrently, and reserving on
                        # the dead pool would leave the fresh pool's
                        # accounting skewed and the request unbacked
                        decoder = api.decoder
                        pool = booked["pool"] = decoder.pool
                        need = booked["need"] = decoder.worst_case_pages(
                            len(prompt), limit, api.chunk)
                        api.ledger.mark(row, "pool_gated",
                                        pages_reserved=need)
                        if pool.try_reserve(need):
                            booked["reserved"] = True
                            return None
                        return pool.retry_after(need)
                admit_limit = api.effective_max_queue
                verdict = api.health.try_admit(admit_limit,
                                               pool_gate=pool_gate)
                if verdict == "unready":
                    req_span.annotate(outcome="unready")
                    api.ledger.resolve(row, "rejected",
                                       error="unready")
                    reply(self, {"error": api._tripped or "not ready"},
                          code=503,
                          headers=retry_after_headers(api.health))
                    return
                if verdict == "full":
                    req_span.annotate(outcome="rejected")
                    api.ledger.resolve(row, "rejected",
                                       error="queue full")
                    reply(self,
                          {"error": "saturated: %d requests in flight"
                           % admit_limit},
                          code=429,
                          headers=retry_after_headers(api.health))
                    return
                if isinstance(verdict, tuple) and verdict[0] == "pool":
                    req_span.annotate(outcome="pool_full")
                    api.ledger.resolve(row, "rejected",
                                       error="kv page pool full")
                    reply(self,
                          {"error": "kv page pool exhausted: need %d "
                           "pages, %d free"
                           % (booked["need"],
                              booked["pool"].free_pages)},
                          code=429,
                          headers={"Retry-After":
                                   "%d" % max(1, round(verdict[1]))})
                    return
                if api.governor is not None:
                    # prewarm trend sensor (actuator c): ADMITTED
                    # requests only — rejections must not heat a
                    # bucket the server never actually serves
                    api.governor.observe_bucket(
                        decoder.bucket_for(len(prompt)))
                staged_at = time.monotonic()
                # slot-timeline linkage survives a disabled tracer:
                # the client's propagated trace id (trace_hint) rides
                # the holder so the occupancy entry still links to the
                # request (span id None — there is no server span)
                trace_ctx = ctx
                if trace_ctx is None and trace_hint:
                    trace_ctx = (trace_hint, None)
                holder = {"event": threading.Event(),
                          "staged_at": staged_at,
                          "deadline": staged_at + deadline_s,
                          "trace": trace_ctx,
                          "tenant": tenant,
                          "ledger_row": row}
                if booked.get("reserved"):
                    holder["pool"] = booked["pool"]
                    holder["pool_reserved"] = booked["need"]
                # tag the staged request's host-side scratch (prompt
                # tokens + the token budget it may produce, int32) for
                # memscope's admission_scratch owner; _resolve drops
                # the tag exactly once. One GIL-atomic dict set.
                from veles_tpu.observe.memscope import get_memscope
                holder["memscope_key"] = id(holder)
                get_memscope().scratch_note(
                    id(holder),
                    (len(prompt) + (budget if budget is not None
                                    else api.decoder.n_tokens)) * 4)
                api._staged.put((prompt, budget, holder))
                api._wake.set()
                trace_headers = {}
                header_value = format_trace_header(req_span.context())
                if header_value:
                    # echo the trace id so the CLIENT can find this
                    # request in the exported span timeline
                    trace_headers[TRACE_HEADER] = header_value
                # the DRIVER owns deadline expiry (it frees the slot);
                # the grace here is only a backstop against a wedged
                # (hung, non-raising) driver thread. The handler then
                # resolves the holder ITSELF so the in-flight gauge is
                # released — otherwise a dead driver would ratchet the
                # gauge up to max_queue and 429 everything forever —
                # and falls through to the shared reply logic (a driver
                # winning the race by a hair still delivers its result).
                if not holder["event"].wait(deadline_s
                                            + api.BACKSTOP_GRACE):
                    api._resolve(holder, "errors",
                                 error="timed out", code=503)
                if "error" in holder:
                    code = holder.get("code", 400)
                    req_span.annotate(outcome="error", code=code)
                    headers = dict(trace_headers)
                    if code in (429, 503):
                        headers.update(retry_after_headers(api.health))
                    reply(self, {"error": holder["error"]}, code=code,
                          headers=headers)
                    return
                req_span.annotate(outcome="completed",
                                  tokens=len(holder["tokens"]))
                reply(self, {"tokens": holder["tokens"]},
                      headers=trace_headers)

        self._httpd, self.port = start_server(
            Handler, self.host, self.port, name="generate-api")
        self._driver = threading.Thread(target=self._drive,
                                        name="generate-driver",
                                        daemon=True)
        self._driver.start()
        self.health.set_ready(True)
        return self

    def stop(self):
        self.health.set_ready(False)
        self._stop.set()
        self._wake.set()
        if self._driver is not None:
            # the driver's finally-block resolves in-flight requests
            # ("server stopped") so no handler blocks out its deadline
            self._driver.join(timeout=10)
            self._driver = None
        if self.governor is not None:
            # outstanding prewarm compiles are non-daemon threads (an
            # XLA compile must never be killed mid-flight); join them
            # AFTER the driver so its final pass cannot spawn a
            # straggler this join would miss
            self.governor.drain_prewarm()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd = None
