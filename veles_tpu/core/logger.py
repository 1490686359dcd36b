"""Class-mixin logging with ANSI colors, file duplication and event spans.

TPU-native re-design of reference ``veles/logger.py:59-332``. Kept: the
``Logger`` mixin giving every object a per-class logger, ``setup_logging``
with a colored console formatter, redirecting/duplicating all logging to a
file, and the ``event()`` span API used by the observability stack. Changed:
event spans are written to a local JSONL file (consumed by the web-status
timeline) instead of MongoDB — no database dependency on a TPU pod host.
"""

import json
import logging
import logging.handlers
import os
import sys
import threading
import time


class ColorFormatter(logging.Formatter):
    """ANSI color console formatter (reference ``logger.py:66-114``)."""

    COLORS = {
        logging.DEBUG: "\033[1;34m",     # blue
        logging.INFO: "\033[1;32m",      # green
        logging.WARNING: "\033[1;33m",   # yellow
        logging.ERROR: "\033[1;31m",     # red
        logging.CRITICAL: "\033[1;41m",  # red background
    }
    RESET = "\033[0m"

    def __init__(self, colorize=True):
        super().__init__(
            "%(asctime)s %(levelname).1s %(name)s: %(message)s",
            "%H:%M:%S")
        self.colorize = colorize

    def format(self, record):
        text = super().format(record)
        if self.colorize:
            color = self.COLORS.get(record.levelno)
            if color:
                return "%s%s%s" % (color, text, self.RESET)
        return text


class Logger:
    """Mixin: every instance gets ``self.logger`` named after its class and
    debug/info/warning/error helpers (reference ``logger.py:59``)."""

    def __init__(self, **kwargs):
        logger_name = kwargs.pop("logger_name", type(self).__name__)
        self._logger_ = logging.getLogger(logger_name)
        super().__init__()

    @property
    def logger(self):
        try:
            if self._logger_ is not None:
                return self._logger_
        except AttributeError:
            pass
        # objects restored from pickle rebuild their logger lazily
        self._logger_ = logging.getLogger(type(self).__name__)
        return self._logger_

    @logger.setter
    def logger(self, value):
        self._logger_ = value

    def change_log_name(self, name):
        self._logger_ = logging.getLogger(name)

    def debug(self, msg, *args, **kwargs):
        self.logger.debug(msg, *args, **kwargs)

    def info(self, msg, *args, **kwargs):
        self.logger.info(msg, *args, **kwargs)

    def warning(self, msg, *args, **kwargs):
        self.logger.warning(msg, *args, **kwargs)

    def error(self, msg, *args, **kwargs):
        self.logger.error(msg, *args, **kwargs)

    def exception(self, msg="Exception", *args, **kwargs):
        self.logger.exception(msg, *args, **kwargs)

    # -- event span API (reference logger.py:264-289) -----------------------
    def event(self, name, etype, **attrs):
        """Record a span event: ``etype`` is "begin", "end" or "single"."""
        assert etype in ("begin", "end", "single"), etype
        get_event_recorder().record(
            name=name, etype=etype, source=type(self).__name__, **attrs)
        # the always-on black box keeps the last events too; lazy
        # import — observe.tracing imports THIS module at its top
        from veles_tpu.observe.flight import get_flight_recorder
        get_flight_recorder().note("event", name=name, etype=etype,
                                   source=type(self).__name__)


_setup_done = False


def setup_logging(level=logging.INFO, colorize=None):
    """Install the colored stderr handler on the root logger
    (reference ``logger.py:116-185``)."""
    global _setup_done
    if colorize is None:
        colorize = sys.stderr.isatty()
    rl = logging.getLogger()
    rl.setLevel(level)
    if not _setup_done:
        handler = logging.StreamHandler(sys.stderr)
        handler.setFormatter(ColorFormatter(colorize))
        rl.addHandler(handler)
        _setup_done = True
    return rl


def duplicate_all_logging_to_file(path, level=logging.DEBUG):
    """Add a file handler mirroring everything (reference ``logger.py:187``)."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    handler = logging.FileHandler(path)
    handler.setLevel(level)
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s: %(message)s"))
    logging.getLogger().addHandler(handler)
    return handler


class MongoLogHandler(logging.Handler):
    """Duplicate every log record into a MongoDB collection (reference
    ``MongoLogHandler``, ``logger.py:292`` — the web dashboard's
    ``logs.html`` read these). pymongo is NOT a hard dependency: the
    default ``client_factory`` imports it lazily and raises a clear
    error if absent; tests and alternative drivers inject their own
    factory returning any object with
    ``client[db][collection].insert_one(doc)``."""

    def __init__(self, addr="127.0.0.1:27017", docid=None,
                 database="veles", collection="logs",
                 client_factory=None, level=logging.DEBUG):
        super().__init__(level)
        if client_factory is None:
            def client_factory(address):
                try:
                    import pymongo
                except ImportError:
                    raise RuntimeError(
                        "MongoDB log duplication needs pymongo installed "
                        "(the JSONL event recorder needs nothing — see "
                        "enable_event_recording)") from None
                return pymongo.MongoClient("mongodb://%s" % address)
        self.docid = docid or "%d" % os.getpid()
        self._collection = client_factory(addr)[database][collection]
        self._emitting = threading.local()
        self.on_close = None  # duplicate_all_logging_to_mongo's detach

    def close(self):
        detach = self.on_close
        self.on_close = None
        if detach is not None:
            detach()
        super().close()

    def emit(self, record):
        # pymongo 4.8+ itself logs DEBUG records during insert_one
        # (command/connection monitoring): without the re-entrancy guard
        # and driver filter, mirroring its records would recurse forever
        if record.name.startswith("pymongo") \
                or getattr(self._emitting, "active", False):
            return
        self._emitting.active = True
        try:
            self._collection.insert_one({
                "session": self.docid,
                "time": record.created,
                "level": record.levelname,
                "logger": record.name,
                "message": record.getMessage(),
            })
        except Exception:
            self.handleError(record)
        finally:
            self._emitting.active = False


def duplicate_all_logging_to_mongo(addr, docid=None, client_factory=None,
                                   background=True):
    """Mirror the root logger into MongoDB (reference ``logger.py:210``)
    and route event spans there too (collection ``events``), correlated
    by the same session docid as the log records.

    ``background=True`` (default) emits through a
    ``QueueHandler``/``QueueListener`` pair so the per-record network
    round trip happens on a listener thread, never blocking the caller
    (a slow/unreachable server would otherwise stall every log call on
    the driver's timeout, serialized through the handler lock).

    Tear down with ``handler.close()`` on the RETURNED handler: it
    detaches the root-logger handler, stops the listener (flushing
    queued records), and unregisters the event sink."""
    handler = MongoLogHandler(addr, docid=docid,
                              client_factory=client_factory)
    root_logger = logging.getLogger()
    listener = queue_handler = event_worker = event_queue = None
    events = handler._collection.database["events"]

    # override the recorder's pid-based session with the handler's docid
    # so veles.logs and veles.events join on the same key (the
    # reference's dashboard correlated them per session)
    if background:
        import queue as queue_mod
        from logging.handlers import QueueHandler, QueueListener

        queue_handler = QueueHandler(queue_mod.SimpleQueue())
        listener = QueueListener(queue_handler.queue, handler)
        listener.start()
        root_logger.addHandler(queue_handler)

        # events go through their own worker for the same reason the
        # log records do: Logger.event() must never block on a Mongo
        # round trip (or the driver's multi-second timeout)
        event_queue = queue_mod.SimpleQueue()

        def sink(attrs):
            event_queue.put(dict(attrs, session=handler.docid))

        def drain():
            warned = False
            while True:
                item = event_queue.get()
                if item is None:
                    return
                try:
                    events.insert_one(item)
                except Exception:
                    # the span is dropped (the JSONL recorder still has
                    # it) — but say so ONCE: in this mode sink() only
                    # enqueues, so record()'s warn-once can never fire
                    if not warned:
                        warned = True
                        logging.getLogger("MongoLogHandler").exception(
                            "event insert failed (further failures "
                            "silent; spans remain in the JSONL log)")

        event_worker = threading.Thread(target=drain,
                                        name="mongo-events", daemon=True)
        event_worker.start()
    else:
        root_logger.addHandler(handler)

        def sink(attrs):
            events.insert_one(dict(attrs, session=handler.docid))

    get_event_recorder().add_sink(sink)

    def detach():
        get_event_recorder().remove_sink(sink)
        if listener is not None:
            root_logger.removeHandler(queue_handler)
            listener.stop()
            event_queue.put(None)  # drains queued spans first (FIFO)
            event_worker.join(timeout=10)
            if event_worker.is_alive():
                # a stuck driver timeout can outlive the join budget —
                # the flush promise must fail loudly, not silently
                logging.getLogger("MongoLogHandler").warning(
                    "mongo event queue not fully flushed within 10s; "
                    "remaining spans may be lost (daemon worker still "
                    "inserting)")
        else:
            root_logger.removeHandler(handler)

    handler.on_close = detach
    return handler


class EventRecorder:
    """Append-only JSONL event-span log, the TPU-era stand-in for the
    reference's MongoDB event store (``logger.py:210-289``). Spans carry a
    session id and wall-clock time; the web-status timeline reads this file.
    """

    #: pre-open buffer cap: a recorder CONFIGURED with a path whose
    #: open() never comes (misordered startup, crashed initializer)
    #: must not grow its buffer forever — beyond this the OLDEST spans
    #: drop (the recent ones are the ones worth flushing) with one
    #: warning
    MAX_BUFFER = 10000

    def __init__(self, path=None, session=None):
        self.path = path
        self.session = session or "%d" % os.getpid()
        self._lock = threading.Lock()
        self._fd = None
        self._buffer = []
        self._buffer_dropped = 0
        self._sinks = []
        self._sink_warned = set()
        self.enabled = path is not None

    def add_sink(self, sink):
        """Register an extra span consumer (e.g. the Mongo duplicator);
        ``sink(attrs_dict)`` is called for every recorded span. Sink
        exceptions are swallowed (logged once per sink) and the sink
        KEPT — a transient outage must neither kill the run nor
        permanently disable duplication."""
        with self._lock:
            self._sinks.append(sink)
            self._sink_warned.discard(id(sink))

    def remove_sink(self, sink):
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)
            self._sink_warned.discard(id(sink))

    def open(self, path):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self.path = path
        self._fd = open(path, "a", buffering=1)
        self.enabled = True
        with self._lock:
            for line in self._buffer:
                self._fd.write(line)
            self._buffer.clear()

    def record(self, **attrs):
        if self._fd is None and not self.enabled and not self._sinks:
            # no file, no pre-open buffer, no sink: nothing would read
            # the line, so it is not serialised (a traced window of
            # the profiler calls this twice per span)
            return
        attrs.setdefault("time", time.time())
        # monotonic stamp: what the Chrome trace exporter orders and
        # measures by (wall time can step; span durations must not)
        attrs.setdefault("mono", time.monotonic())
        attrs.setdefault("session", self.session)
        line = json.dumps(attrs, default=str) + "\n"
        warn_drop = False
        with self._lock:
            if self._fd is not None:
                self._fd.write(line)
            elif self.enabled:
                if len(self._buffer) >= self.MAX_BUFFER:
                    # drop-oldest: the spans worth flushing at open()
                    # are the recent ones
                    del self._buffer[0]
                    warn_drop = self._buffer_dropped == 0
                    self._buffer_dropped += 1
                self._buffer.append(line)
        if warn_drop:  # once — this can be a high-frequency path
            logging.getLogger("EventRecorder").warning(
                "pre-open event buffer full (%d spans); dropping the "
                "oldest from here on — call open()/"
                "enable_event_recording to flush (reported once)",
                self.MAX_BUFFER)
        with self._lock:
            sinks = list(self._sinks)
        for sink in sinks:
            try:
                sink(attrs)
            except Exception:
                with self._lock:
                    warn = id(sink) not in self._sink_warned
                    self._sink_warned.add(id(sink))
                if warn:  # once per sink — spans can be high-frequency
                    logging.getLogger("EventRecorder").exception(
                        "event sink failed (kept; reported once)")

    def close(self):
        with self._lock:
            if self._fd is not None:
                self._fd.close()
                self._fd = None


_event_recorder = EventRecorder()


def get_event_recorder():
    return _event_recorder


def enable_event_recording(path):
    _event_recorder.open(path)
    return _event_recorder
