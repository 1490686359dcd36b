"""Observability-layer tests (docs/observability.md): MetricsRegistry
semantics + Prometheus exposition, the disabled-path overhead guard,
EventRecorder buffering, dashboard event tailing, Chrome trace export,
and end-to-end trace propagation through a real GenerateAPI request and
a real fleet round trip. ``make metrics`` runs this module standalone."""

import glob
import json
import os
import threading
import time
import urllib.request

import numpy
import pytest

from veles_tpu.core.logger import EventRecorder
from veles_tpu.observe.metrics import MetricsRegistry, bridge
from veles_tpu.observe.tracing import (NULL_SPAN, Tracer,
                                       parse_trace_header)
from veles_tpu.observe.trace_export import (chrome_trace,
                                            export_chrome_trace,
                                            load_events, span_tree)


def get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def post(url, payload, headers=None, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers=dict({"Content-Type": "application/json"},
                     **(headers or {})))
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode()), dict(resp.headers)


class TestMetricsRegistry:
    def test_concurrent_counters_exact(self):
        """N threads hammering the same counter (and a labeled series)
        must land on the exact total — the registry's one lock is the
        whole consistency story."""
        registry = MetricsRegistry(enabled=True)
        threads_n, per_thread = 8, 2000

        def work(i):
            for _ in range(per_thread):
                registry.incr("veles_test_total")
                registry.incr("veles_test_labeled_total", 2,
                              labels={"worker": str(i % 2)})
                registry.observe("veles_test_seconds", 0.01,
                                 buckets=(0.005, 0.05))

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        text = registry.expose()
        assert "veles_test_total %d" % (threads_n * per_thread) in text
        for worker in ("0", "1"):
            assert ('veles_test_labeled_total{worker="%s"} %d'
                    % (worker, threads_n // 2 * per_thread * 2)) in text
        assert ("veles_test_seconds_count %d"
                % (threads_n * per_thread)) in text

    def test_exposition_format(self):
        registry = MetricsRegistry(enabled=True)
        registry.incr("veles_req_total", 3,
                      labels={"path": 'a"b\\c\nd'},
                      help="requests\nby path")
        registry.set("veles_up", 1, help="liveness")
        registry.observe("veles_lat_seconds", 0.03,
                         buckets=(0.01, 0.1, 1.0))
        registry.observe("veles_lat_seconds", 5.0,
                         buckets=(0.01, 0.1, 1.0))
        text = registry.expose()
        lines = text.splitlines()
        # HELP escaping: newline survives as \n, backslash doubled
        assert "# HELP veles_req_total requests\\nby path" in lines
        assert "# TYPE veles_req_total counter" in lines
        assert "# TYPE veles_up gauge" in lines
        assert "# TYPE veles_lat_seconds histogram" in lines
        # label value escaping: quote, backslash and newline
        assert ('veles_req_total{path="a\\"b\\\\c\\nd"} 3') in lines
        # histogram: cumulative monotone buckets, +Inf == count, sum
        buckets = [line for line in lines
                   if line.startswith("veles_lat_seconds_bucket")]
        counts = [int(line.rsplit(" ", 1)[1]) for line in buckets]
        assert counts == sorted(counts), buckets
        assert buckets[-1].startswith(
            'veles_lat_seconds_bucket{le="+Inf"}')
        assert counts[-1] == 2
        assert "veles_lat_seconds_count 2" in lines
        assert "veles_lat_seconds_sum 5.03" in lines

    def test_bridge_unregisters_dead_source(self):
        registry = MetricsRegistry(enabled=True)

        class Source:
            pass

        source = Source()
        bridge(registry, source,
               lambda reg, live: reg.set("veles_src_up", 1))
        assert "veles_src_up 1" in registry.expose()
        assert len(registry._collectors) == 1
        del source
        import gc
        gc.collect()
        registry.expose()  # the dead collector unregisters itself
        assert registry._collectors == []

    def test_broken_collector_never_breaks_exposition(self):
        registry = MetricsRegistry(enabled=True)
        registry.add_collector(lambda: 1 / 0)
        registry.incr("veles_ok_total")
        assert "veles_ok_total 1" in registry.expose()

    def test_kind_collision_drops_the_write(self):
        """A scalar sample aimed at a histogram family (e.g. a skewed
        fleet slave re-using a histogram name) must be DROPPED, not
        poison every later expose()."""
        registry = MetricsRegistry(enabled=True)
        registry.observe("veles_h_seconds", 0.1, buckets=(1.0,))
        registry.counter_set("veles_h_seconds", 7)
        registry.incr("veles_h_seconds")
        registry.set("veles_h_seconds", 3)
        registry.observe("veles_c_total", 0.5, buckets=(1.0,))
        registry.incr("veles_c_total", 2)  # dropped: histogram exists
        text = registry.expose()  # must not raise
        assert "veles_h_seconds_count 1" in text
        assert "veles_c_total_count 1" in text
        assert "\nveles_c_total 2" not in text
        registry.histogram_summary()  # must not raise either

    def test_hostile_slave_rows_cannot_break_master_exposition(self):
        """The fleet piggyback path: rows with exposition-breaking
        metric/label names are rejected by slave_metrics; only label
        VALUES (escaped) get through."""
        from veles_tpu.fleet.server import Server, SlaveDescription

        server = Server.__new__(Server)
        slave = SlaveDescription("slave-1", {})
        server.slaves = {"slave-1": slave}
        slave.metrics_rows = [
            ["veles_ok_total", "counter",
             [["path", 'a"} evil{b="1']], 5],          # hostile VALUE: ok
            ['veles_x{a="1"} 9 #', "counter", [], 5],  # hostile NAME
            ["veles_y_total", "counter",
             [['a"} evil{b="1', "v"]], 5],             # hostile label KEY
            ["veles_z_total", "counter", [["slave", "slave-9"]], 5],
            ["veles_b_total", "counter", [], True],    # bool is not a number
            "not-a-row",
        ]
        clean = server.slave_metrics()
        assert list(clean) == ["slave-1"]
        assert [row[0] for row in clean["slave-1"]] == ["veles_ok_total"]
        registry = MetricsRegistry(enabled=True)
        from veles_tpu.observe.metrics import publish_fleet
        server.fleet_status = lambda: {"slaves": [], "queued_jobs": 0}
        publish_fleet(registry, server)
        text = registry.expose()
        # the hostile value survives only ESCAPED inside one label —
        # the quote that would have closed the label set is \" —
        # so the line still parses as a single sample
        assert ('veles_ok_total{path="a\\"} evil{b=\\"1",'
                'slave="slave-1"} 5') in text
        assert "veles_y_total" not in text
        assert "veles_z_total" not in text

    def test_piggyback_rows_bounded_and_stale_slaves_pruned(self):
        from veles_tpu.fleet.server import Server, SlaveDescription
        from veles_tpu.observe.metrics import publish_fleet

        server = Server.__new__(Server)
        one, two = (SlaveDescription(sid, {})
                    for sid in ("slave-1", "slave-2"))
        server.slaves = {"slave-1": one, "slave-2": two}
        # volume bound: a hostile slave's giant snapshot truncates
        one.metrics_rows = [
            ["veles_r%d_total" % i, "counter", [["v", "x" * 4096]], i]
            for i in range(Server.METRICS_MAX_ROWS + 500)]
        two.metrics_rows = [["veles_t_total", "counter", [], 1]]
        clean = server.slave_metrics()
        assert len(clean["slave-1"]) == Server.METRICS_MAX_ROWS
        assert all(len(labels["v"]) <= Server.METRICS_MAX_VALUE_LEN
                   for _, _, labels, _ in clean["slave-1"])
        # churn bound: a departed slave's re-exported series retire
        registry = MetricsRegistry(enabled=True)
        server.fleet_status = lambda: {
            "slaves": [s.as_dict() for s in server.slaves.values()],
            "queued_jobs": 0}
        publish_fleet(registry, server)
        assert 'slave="slave-2"' in registry.expose()
        del server.slaves["slave-2"]
        publish_fleet(registry, server)
        text = registry.expose()
        assert 'slave="slave-2"' not in text
        assert 'veles_t_total' not in text
        assert 'slave="slave-1"' in text


def _assert_valid_exposition(text):
    """Every scrape must be a parseable exposition: sample lines match
    the format, and each histogram's cumulative buckets are monotone
    with +Inf equal to the count — under ANY interleaving with
    writers."""
    import re

    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})? '
        r'(-?[0-9.eE+]+|[+-]Inf|NaN)$')
    buckets = {}  # (name, label-prefix) -> [counts...]
    counts = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        assert sample_re.match(line), "unparseable sample: %r" % line
        name = line.split("{")[0].split(" ")[0]
        if name.endswith("_bucket"):
            labels = line[len(name):line.rindex("}") + 1]
            series = re.sub(r',?le="[^"]*"', "", labels)
            buckets.setdefault((name, series), []).append(
                int(line.rsplit(" ", 1)[1]))
        elif name.endswith("_count"):
            series = line[len(name):].rsplit(" ", 1)[0]
            counts[(name[:-len("_count")], series)] = int(
                line.rsplit(" ", 1)[1])
    for (name, series), values in buckets.items():
        assert values == sorted(values), \
            "non-monotone buckets for %s%s: %r" % (name, series, values)
        total = counts.get((name[:-len("_bucket")], series))
        if total is not None:
            assert values[-1] == total, (name, series, values, total)


class TestConcurrentScrape:
    """ISSUE 5 satellite: N writer threads hammering counters, gauges
    and histograms while M scrapers read must yield a parseable
    exposition with monotone cumulative buckets on EVERY scrape — the
    registry's one lock is the whole consistency story and this is the
    test that would catch a torn histogram slot."""

    def test_scrapes_stay_consistent_under_mutation(self):
        registry = MetricsRegistry(enabled=True)
        stop = threading.Event()
        failures = []
        writes = [0] * 4

        def writer(i):
            while not stop.is_set():
                registry.incr("veles_cw_total",
                              labels={"w": str(i % 2)})
                registry.observe("veles_cw_seconds", 0.003 * (i + 1),
                                 buckets=(0.005, 0.01, 0.05))
                registry.set("veles_cw_gauge", i,
                             labels={"w": str(i)})
                writes[i] += 1

        def scraper():
            while not stop.is_set():
                try:
                    _assert_valid_exposition(registry.expose())
                except AssertionError as exc:
                    failures.append(exc)
                    stop.set()
                    return

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        threads += [threading.Thread(target=scraper) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.8)
        stop.set()
        for t in threads:
            t.join()
        assert not failures, failures[0]
        # quiesced, the totals are exact: nothing was lost or torn
        text = registry.expose()
        _assert_valid_exposition(text)
        assert "veles_cw_seconds_count %d" % sum(writes) in text
        total = sum(writes)
        got = sum(int(line.rsplit(" ", 1)[1])
                  for line in text.splitlines()
                  if line.startswith("veles_cw_total{"))
        assert got == total

    def test_openmetrics_scrapes_stay_consistent_with_exemplars(self):
        """ISSUE 10 satellite: the same hammer with exemplar-carrying
        observations and openmetrics scrapers — every scrape must stay
        parseable after stripping the exemplar suffixes, buckets
        monotone, and every exemplar line well-formed."""
        import re

        exemplar_re = re.compile(
            r' # \{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
            r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\} '
            r'[-+0-9.eE]+ [-+0-9.eE]+$')
        registry = MetricsRegistry(enabled=True)
        stop = threading.Event()
        failures = []

        def writer(i):
            n = 0
            while not stop.is_set():
                registry.observe(
                    "veles_om_seconds", 0.002 * (i + 1),
                    buckets=(0.005, 0.01),
                    exemplar={"trace_id": "t%d-%d" % (i, n)})
                n += 1

        def scraper():
            while not stop.is_set():
                try:
                    text = registry.expose(openmetrics=True)
                    assert text.rstrip().endswith("# EOF")
                    stripped = []
                    for line in text.splitlines():
                        if line == "# EOF":
                            continue
                        cut = line.find(" # {")
                        if cut != -1:
                            assert line.startswith(
                                "veles_om_seconds_bucket"), line
                            assert exemplar_re.search(line), line
                            line = line[:cut]
                        stripped.append(line)
                    _assert_valid_exposition("\n".join(stripped) + "\n")
                except AssertionError as exc:
                    failures.append(exc)
                    stop.set()
                    return

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(3)]
        threads += [threading.Thread(target=scraper) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.6)
        stop.set()
        for t in threads:
            t.join()
        assert not failures, failures[0]


class TestExemplars:
    """ISSUE 10 satellite: OpenMetrics exemplars on the latency
    histograms — exemplars appear ONLY on histogram bucket lines, only
    on openmetrics-negotiated expositions, with the label set bounded
    per the spec; the plain-Prometheus fallback stays parseable."""

    def _registry(self):
        registry = MetricsRegistry(enabled=True)
        registry.observe("veles_ex_seconds", 0.003,
                         buckets=(0.005, 0.01),
                         exemplar={"trace_id": "abc123"})
        registry.observe("veles_ex_seconds", 99.0,
                         buckets=(0.005, 0.01),
                         exemplar={"trace_id": "def456"})
        registry.incr("veles_ex_total", 2)
        registry.set("veles_ex_gauge", 1.0)
        return registry

    def test_exemplars_only_on_histogram_buckets(self):
        text = self._registry().expose(openmetrics=True)
        exemplar_lines = [line for line in text.splitlines()
                          if " # {" in line]
        assert len(exemplar_lines) == 2  # one per bucket hit (incl +Inf)
        for line in exemplar_lines:
            assert line.startswith("veles_ex_seconds_bucket"), line
        assert 'le="0.005"' in exemplar_lines[0] \
            and 'trace_id="abc123"' in exemplar_lines[0]
        assert 'le="+Inf"' in exemplar_lines[1] \
            and 'trace_id="def456"' in exemplar_lines[1]
        # counters/gauges never carry exemplars, and the exposition
        # terminates with the OpenMetrics EOF marker
        for line in text.splitlines():
            if line.startswith(("veles_ex_total", "veles_ex_gauge")):
                assert " # {" not in line
        assert text.rstrip().endswith("# EOF")
        # OpenMetrics counter FAMILIES drop the _total sample suffix
        # (a modern Prometheus negotiates openmetrics by default and
        # would refuse the 0.0.4 spelling); samples keep it
        assert "# TYPE veles_ex counter" in text
        assert "# TYPE veles_ex_total counter" not in text
        assert "\nveles_ex_total 2" in text
        # ...while the plain exposition keeps the 0.0.4 spelling
        assert "# TYPE veles_ex_total counter" in \
            self._registry().expose()

    def test_plain_scrape_fallback_is_parseable(self):
        text = self._registry().expose()
        assert " # {" not in text and "# EOF" not in text
        _assert_valid_exposition(text)

    def test_exemplar_label_set_bounded_and_validated(self):
        from veles_tpu.observe.metrics import EXEMPLAR_MAX_RUNES

        registry = MetricsRegistry(enabled=True)
        # oversized label set: the exemplar is DROPPED, the
        # observation is kept
        registry.observe("veles_big_seconds", 0.001,
                         buckets=(0.01,),
                         exemplar={"trace_id":
                                   "x" * (EXEMPLAR_MAX_RUNES + 1)})
        # invalid label name / the reserved "le": dropped too
        registry.observe("veles_big_seconds", 0.002, buckets=(0.01,),
                         exemplar={"bad name": "v"})
        registry.observe("veles_big_seconds", 0.003, buckets=(0.01,),
                         exemplar={"le": "0.01"})
        text = registry.expose(openmetrics=True)
        assert " # {" not in text
        assert "veles_big_seconds_count 3" in text

    def test_http_accept_negotiation(self, observability):
        """A scraper advertising application/openmetrics-text gets
        exemplars + # EOF; a plain scrape of the SAME surface stays
        0.0.4 text."""
        import urllib.request
        from veles_tpu.core.httpd import serve_metrics  # noqa: F401
        from veles_tpu.observe.metrics import get_metrics_registry
        from veles_tpu.serving import RESTfulAPI
        from veles_tpu.dummy import DummyWorkflow

        registry = get_metrics_registry()
        registry.observe("veles_neg_seconds", 0.002, buckets=(0.01,),
                         exemplar={"trace_id": "feed01"})
        api = RESTfulAPI(DummyWorkflow(name="neg-wf"), port=0)
        api.feed = lambda *a: None
        api.requests = []
        api.initialize()
        try:
            url = "http://127.0.0.1:%d/metrics" % api.port
            plain = get(url)
            assert " # {" not in plain and "# EOF" not in plain
            req = urllib.request.Request(
                url, headers={"Accept": "application/openmetrics-text"})
            with urllib.request.urlopen(req, timeout=10) as resp:
                om = resp.read().decode()
                ctype = resp.headers.get("Content-Type", "")
            assert "application/openmetrics-text" in ctype
            assert 'trace_id="feed01"' in om
            assert om.rstrip().endswith("# EOF")
        finally:
            api.stop()


class TestMetricNamingLint:
    """ISSUE 5 satellite, deduped by ISSUE 13: the AST walk that lived
    here moved into the shared analyzer rule (veles_tpu/analyze/
    rules.py, ``metric.naming``/``metric.help`` — `veles_tpu analyze`
    gates it in CI). This wrapper pins that (1) the shared rule still
    FIRES on a seeded violation fixture, and (2) the tree is clean —
    plus the vacuous-scan guard: the instrumented families must
    actually be in the scan."""

    def test_rule_fires_on_seeded_violation(self):
        from veles_tpu.analyze import run_analysis

        fixture = os.path.join(os.path.dirname(os.path.abspath(
            __file__)), "fixtures", "analyze", "metric_naming.py")
        findings, errors = run_analysis([fixture],
                                        rule_filter="metric.naming")
        assert not errors
        assert len(findings) == 1
        assert findings[0].rule == "metric.naming"
        assert "_total" in findings[0].message

    def test_conventions_hold_everywhere(self):
        from veles_tpu.analyze import run_analysis
        from veles_tpu.analyze.rules import iter_metric_calls
        import ast

        package = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "veles_tpu")
        findings, errors = run_analysis([package], rule_filter="metric")
        assert not errors
        assert findings == [], "\n".join(
            f.format(relative_to=package) for f in findings)
        # the instrumented families must actually be in the scan —
        # an empty scan would "pass" vacuously
        names = set()
        for path in glob.glob(os.path.join(package, "**", "*.py"),
                              recursive=True):
            for _, _, name, _, _ in iter_metric_calls(
                    ast.parse(open(path).read())):
                names.add(name)
        assert "veles_serving_requests_total" in names
        assert "veles_xla_compiles_total" in names
        assert "veles_device_memory_bytes" in names


class TestOverheadGuard:
    """The `make metrics` guard (ISSUE satellite): disabled-path
    span()/incr() must be structural no-ops so observability can never
    silently tax the PR-3 serving hot path."""

    def test_disabled_tracer_returns_shared_null_span(self):
        tracer = Tracer(enabled=False)
        spans = {id(tracer.span("a")), id(tracer.span("b", x=1)),
                 id(tracer.event("c"))}
        assert spans == {id(NULL_SPAN)}
        with tracer.span("a") as span:
            assert span is NULL_SPAN
            assert span.context() is None

    def test_disabled_registry_mutates_nothing(self):
        registry = MetricsRegistry(enabled=False)
        registry.incr("veles_x_total")
        registry.set("veles_g", 2)
        registry.observe("veles_h_seconds", 0.1)
        registry.counter_set("veles_c_total", 9)
        assert registry._families == {}
        assert registry.expose() == "\n"

    def test_decoder_disabled_path_uses_null_span(self):
        from veles_tpu.parallel.transformer_step import (
            init_transformer_params)
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        rng = numpy.random.RandomState(0)
        params = init_transformer_params(rng, 1, 8, 2, 7)
        table = jnp.asarray(rng.randn(7, 8).astype(numpy.float32))
        dec = ContinuousDecoder(params, table, 2, slots=1, max_len=32,
                                n_tokens=2)
        dec._tracer = Tracer(enabled=False)
        dec.metrics = MetricsRegistry(enabled=False)
        assert dec._span("decode.dispatch", [0]) is NULL_SPAN
        dec.submit([1, 2])
        dec.run_until_drained(max_steps=8)
        assert dec.metrics._families == {}

    def test_flight_default_on_path_stays_structurally_noop(self):
        """The always-on flight recorder must pass the SAME guard: the
        decoder's default-on notes touch neither the registry nor the
        tracer, ring memory is bounded by maxlen, and a note is one
        flag check + append (no locks, no I/O)."""
        from veles_tpu.observe.flight import FlightRecorder
        from veles_tpu.parallel.transformer_step import (
            init_transformer_params)
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        rng = numpy.random.RandomState(0)
        params = init_transformer_params(rng, 1, 8, 2, 7)
        table = jnp.asarray(rng.randn(7, 8).astype(numpy.float32))
        dec = ContinuousDecoder(params, table, 2, slots=1, max_len=32,
                                n_tokens=2)
        dec._tracer = Tracer(enabled=False)
        dec.metrics = MetricsRegistry(enabled=False)
        dec.flight = FlightRecorder(capacity=4)  # default-ON
        dec.submit([1, 2])
        dec.run_until_drained(max_steps=8)
        # the ring recorded the dispatch path...
        kinds = {e["kind"] for e in dec.flight.entries()}
        assert "admit" in kinds
        # ...bounded, and with ZERO registry/tracer traffic
        assert len(dec.flight.entries()) <= 4
        assert dec.metrics._families == {}

    def test_history_disabled_path_stays_structurally_noop(self):
        """ISSUE 12: the metric flight recorder obeys the same guard.
        A disabled registry's sample() returns before running any
        collector (the no-scrape fast path stays allocation-free), a
        history over it books nothing — not a pass, not a rule
        evaluation — and the store carries no lock attribute anywhere
        (the flight-ring record discipline)."""
        from veles_tpu.observe.history import (AnomalyRule,
                                               IncidentRecorder,
                                               MetricHistory)

        registry = MetricsRegistry(enabled=False)
        ran = []
        registry.add_collector(lambda: ran.append(1))
        assert registry.sample() == ()
        assert ran == []
        rule = AnomalyRule("burn", "veles_b", threshold=0.0,
                           for_samples=1)
        history = MetricHistory(
            registry=registry, rules=[rule],
            incidents=IncidentRecorder(cooldown_s=3600.0))
        assert history.sample() is False
        assert history.samples_total == 0
        assert history.series_list() == []
        assert rule.streak == 0 and history.anomalies_total == 0
        assert not any("lock" in attr.lower()
                       for attr in vars(history))

    def test_memscope_record_path_stays_structurally_noop(self):
        """ISSUE 20: the HBM attribution plane obeys the same guard.
        The record-path hooks (scratch tags, lifecycle edges, pool
        points) are flag checks + GIL-atomic container ops: a scope
        carries no lock attribute anywhere, the hooks never touch a
        registry, and a disabled scope's hooks mutate nothing."""
        from veles_tpu.observe.memscope import MemScope

        scope = MemScope(leak_min_bytes=1, limit_bytes=None)
        assert not any("lock" in attr.lower() for attr in vars(scope))
        registry = MetricsRegistry(enabled=False)
        scope.scratch_note("r1", 4096)
        scope.edge_begin("breaker_rebuild")
        scope.edge_end("breaker_rebuild")
        scope.scratch_drop("r1")

        class _Pool:
            used_pages = 3
            free_pages = 5

        scope.note_pool(_Pool())
        # record-path hooks generated zero registry traffic (publish
        # is the scrape-time seam, and a disabled registry's family
        # mutators are no-ops anyway)
        assert registry._families == {}
        scope.publish(registry)
        assert registry._families == {}
        # rings are bounded; tallies recorded the activity
        assert scope.edges_total == 1
        assert len(scope._pool_points) == 1
        # a disabled scope's hooks are structural no-ops
        scope.enabled = False
        scope.scratch_note("r2", 1)
        scope.edge_begin("swap_params")
        assert scope.edge_end("swap_params") is None
        scope.note_pool(_Pool())
        assert "r2" not in scope._scratch
        assert len(scope._open_edges) == 0
        assert len(scope._pool_points) == 1

    def test_request_ledger_null_and_default_paths(self):
        """ISSUE 10: with NO ledger attached (the default) a decoder
        leaves the process ledger untouched — one attribute check per
        dispatch; with one attached, a full request costs bounded ring
        appends only, with ZERO registry/tracer traffic and no lock
        attribute anywhere on the record path."""
        from veles_tpu.observe.reqledger import (RequestLedger,
                                                 get_request_ledger)
        from veles_tpu.parallel.transformer_step import (
            init_transformer_params)
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        rng = numpy.random.RandomState(0)
        params = init_transformer_params(rng, 1, 8, 2, 7)
        table = jnp.asarray(rng.randn(7, 8).astype(numpy.float32))
        before = (get_request_ledger().staged_total,
                  get_request_ledger().resolved_total)
        dec = ContinuousDecoder(params, table, 2, slots=1, max_len=32,
                                n_tokens=2)
        assert dec.ledger is None
        dec.submit([1, 2])
        dec.run_until_drained(max_steps=8)
        assert (get_request_ledger().staged_total,
                get_request_ledger().resolved_total) == before
        # attached: rows record through GIL-atomic appends alone — the
        # ledger holds no lock object at all (the structural guarantee
        # behind "no locks on the record path")
        ledger = RequestLedger(capacity=2)
        assert not any("lock" in attr.lower()
                       for attr in vars(ledger))
        dec = ContinuousDecoder(params, table, 2, slots=1, max_len=32,
                                n_tokens=2, ledger=ledger)
        dec._tracer = Tracer(enabled=False)
        dec.metrics = MetricsRegistry(enabled=False)
        for i in range(4):
            row = ledger.stage(api="guard", prompt_len=2)
            dec.ledger_link(dec.submit([1, 2]), row)
            dec.run_until_drained(max_steps=8)
            ledger.resolve(row, "completed")
        assert dec.metrics._families == {}
        assert len(ledger.slowest(10)) == 2  # ring bounded
        assert ledger.resolved_total == 4
        (last,) = ledger.slowest(1)
        assert [s[0] for s in last["stages"]] == [
            "staged", "admitted", "first_token", "resolved"]

    def test_instrument_disabled_tracker_is_pure_delegation(self):
        from veles_tpu.observe.xla_stats import (CompileTracker,
                                                 instrument)
        import veles_tpu.observe.xla_stats as xla_stats_mod
        import jax
        import jax.numpy as jnp

        saved = xla_stats_mod._tracker
        tracker = CompileTracker(enabled=False)
        xla_stats_mod._tracker = tracker
        try:
            fn = instrument("veles_test_prog",
                            jax.jit(lambda x: x + 1))
            out = fn(jnp.ones(3))
            assert float(out.sum()) == 6.0
            assert tracker._compiles == {} and tracker._hits == {}
        finally:
            xla_stats_mod._tracker = saved

    def test_instrument_non_jit_callable_returned_unwrapped(self):
        from veles_tpu.observe.xla_stats import instrument

        def plain(x):
            return x

        assert instrument("veles_test_plain", plain) is plain


class TestCompileTracker:
    def test_compiles_hits_and_flops_book_per_program(self):
        from veles_tpu.observe.xla_stats import CompileTracker, instrument
        import veles_tpu.observe.xla_stats as xla_stats_mod
        import jax
        import jax.numpy as jnp

        saved = xla_stats_mod._tracker
        tracker = CompileTracker(enabled=True)
        xla_stats_mod._tracker = tracker
        try:
            fn = instrument("prog", jax.jit(lambda x: x * 2.0))
            fn(jnp.ones(4))          # compile (shape 1)
            fn(jnp.ones(4))          # hit
            fn(jnp.ones(8))          # compile (shape 2)
            assert tracker._compiles == {"prog": 2}
            assert tracker._hits == {"prog": 1}
            assert tracker._compile_seconds["prog"] > 0
            # Lowered.cost_analysis FLOPs: 8 for the second shape
            assert tracker._flops["prog"] == 8.0
        finally:
            xla_stats_mod._tracker = saved

    def test_recompilation_storm_detected_and_warned_once(self, caplog):
        import logging

        from veles_tpu.observe.xla_stats import CompileTracker

        tracker = CompileTracker(enabled=True)
        with caplog.at_level(logging.WARNING, logger="CompileTracker"):
            for _ in range(2 * tracker.STORM_THRESHOLD):
                tracker.record_compile("churner", 0.01)
        assert tracker._storms == {"churner": 2}
        warnings = [r for r in caplog.records
                    if "recompilation storm" in r.getMessage()]
        assert len(warnings) == 1  # warn-once, counter keeps counting

    def test_mfu_published_from_flops_and_step_ema(self):
        from veles_tpu.core.config import root
        from veles_tpu.observe.xla_stats import CompileTracker

        tracker = CompileTracker(enabled=True)
        tracker.set_program_flops("prog", 2e9)
        tracker.observe_step("prog", 0.01)  # 200 GFLOP/s
        saved = root.common.observe.get("peak_tflops", None)
        root.common.observe.peak_tflops = 1.0  # 1 TFLOP/s peak
        try:
            registry = MetricsRegistry(enabled=True)
            tracker.publish(registry)
            text = registry.expose()
            assert 'veles_xla_program_flops{program="prog"} 2000000000' \
                in text
            assert 'veles_mfu_ratio{program="prog"} 0.2' in text
        finally:
            root.common.observe.peak_tflops = saved

    def test_peak_table_matches_device_kind_exactly(self):
        """Exact keys: "TPU v5" (the v5p) must not claim an unknown v5
        kind by substring; an unlisted TPU kind raises instead of
        yielding a missing MFU key; the CPU has no peak without the
        override."""
        from veles_tpu.observe import xla_stats

        assert xla_stats.peak_tflops("TPU v5 lite") == 197.0
        assert xla_stats.peak_tflops("TPU v5") == 459.0
        with pytest.raises(LookupError, match="TPU v5 ultra"):
            xla_stats.peak_tflops("TPU v5 ultra")
        assert xla_stats.peak_tflops() is None  # the CPU platform
        assert all(source for _, source in
                   xla_stats.PEAK_BF16_TFLOPS.values())

    def test_device_memory_gauges_exist_on_every_backend(self):
        from veles_tpu.observe.xla_stats import publish_device_stats

        registry = MetricsRegistry(enabled=True)
        publish_device_stats(registry)
        text = registry.expose()
        # CPU has no allocator report: the live-bytes fallback still
        # gives the family (TPU reports bytes_in_use/peak/limit)
        assert "veles_device_memory_bytes" in text
        assert 'kind="' in text


class TestEventRecorderBuffer:
    def test_preopen_buffer_capped_drop_oldest(self, tmp_path,
                                               monkeypatch):
        """A recorder configured with a path but never open()ed must
        cap its buffer (drop-oldest) instead of growing forever."""
        monkeypatch.setattr(EventRecorder, "MAX_BUFFER", 10)
        rec = EventRecorder(path=str(tmp_path / "never-opened.jsonl"))
        for i in range(25):
            rec.record(name="span-%d" % i, etype="single")
        assert len(rec._buffer) == 10
        assert rec._buffer_dropped == 15
        kept = [json.loads(line)["name"] for line in rec._buffer]
        assert kept == ["span-%d" % i for i in range(15, 25)]
        # a late open() flushes exactly the surviving tail
        out = tmp_path / "opened.jsonl"
        rec.open(str(out))
        rec.close()
        names = [json.loads(line)["name"]
                 for line in out.read_text().splitlines()]
        assert names == kept

    def test_record_carries_monotonic_stamp(self, tmp_path):
        rec = EventRecorder()
        rec.open(str(tmp_path / "events.jsonl"))
        before = time.monotonic()
        rec.record(name="x", etype="single")
        rec.close()
        event = json.loads(
            (tmp_path / "events.jsonl").read_text().splitlines()[0])
        assert before <= event["mono"] <= time.monotonic()


class TestTailEvents:
    def test_tail_reads_only_the_end_of_a_multi_mb_file(self, tmp_path):
        from veles_tpu.web_status import WebStatusServer, tail_lines

        path = tmp_path / "events.jsonl"
        n = 40000  # ~4.6 MB of lines
        with open(path, "w") as fout:
            for i in range(n):
                fout.write(json.dumps(
                    {"name": "e%06d" % i, "pad": "x" * 80}) + "\n")
        assert os.path.getsize(path) > 3 * 1024 * 1024
        server = WebStatusServer.__new__(WebStatusServer)
        server.events_path = str(path)
        out = server.tail_events(limit=200)
        assert len(out) == 200
        assert [e["name"] for e in out] == \
            ["e%06d" % i for i in range(n - 200, n)]
        # bounded reads: the backward scan may touch at most the tail
        # window plus one block of slack, never megabytes
        reads = []
        real_read = os.read

        class CountingFile:
            def __init__(self, fobj):
                self._f = fobj

            def __getattr__(self, name):
                return getattr(self._f, name)

            def read(self, size):
                reads.append(size)
                return self._f.read(size)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                self._f.close()

        import builtins
        real_open = builtins.open
        try:
            builtins.open = lambda *a, **k: CountingFile(
                real_open(*a, **k))
            tail_lines(str(path), 200)
        finally:
            builtins.open = real_open
        assert sum(reads) <= 200 * 120 + 2 * 65536, sum(reads)
        del real_read

    def test_tail_shorter_than_limit(self, tmp_path):
        from veles_tpu.web_status import tail_lines

        path = tmp_path / "short.jsonl"
        path.write_text("a\nb\nc\n")
        assert tail_lines(str(path), 200) == ["a", "b", "c"]


class TestTraceExport:
    def test_begin_end_pairs_become_complete_events(self, tmp_path):
        events = [
            {"name": "parent", "etype": "begin", "trace_id": "t1",
             "span_id": "s1", "parent_id": None, "mono": 1.0, "tid": 7,
             "pid": 1},
            {"name": "child", "etype": "begin", "trace_id": "t1",
             "span_id": "s2", "parent_id": "s1", "mono": 1.1, "tid": 7,
             "pid": 1},
            {"name": "child", "etype": "end", "trace_id": "t1",
             "span_id": "s2", "parent_id": "s1", "mono": 1.4, "tid": 7,
             "pid": 1},
            {"name": "mark", "etype": "single", "trace_id": "t1",
             "span_id": "s3", "parent_id": "s1", "mono": 1.2, "tid": 7,
             "pid": 1},
            {"name": "parent", "etype": "end", "trace_id": "t1",
             "span_id": "s1", "parent_id": None, "mono": 2.0, "tid": 7,
             "pid": 1},
        ]
        src = tmp_path / "events.jsonl"
        with open(src, "w") as fout:
            for event in events:
                fout.write(json.dumps(event) + "\n")
        out = tmp_path / "trace.json"
        count = export_chrome_trace(str(src), str(out))
        trace = json.loads(out.read_text())
        assert count == len(trace["traceEvents"])
        spans = [e for e in trace["traceEvents"] if e["ph"] != "M"]
        assert len(spans) == 3
        # the multi-process satellite: process/thread metadata rows
        # ride along so merged traces keep one row per process
        metadata = {e["name"] for e in trace["traceEvents"]
                    if e["ph"] == "M"}
        assert metadata == {"process_name", "thread_name"}
        complete = {e["name"]: e for e in trace["traceEvents"]
                    if e["ph"] == "X"}
        assert set(complete) == {"parent", "child"}
        assert complete["child"]["dur"] == pytest.approx(0.3e6)
        assert complete["parent"]["dur"] == pytest.approx(1.0e6)
        tree = span_tree(trace)["t1"]
        assert tree == {"s1": None, "s2": "s1", "s3": "s1"}

    def test_loader_skips_torn_lines(self, tmp_path):
        src = tmp_path / "events.jsonl"
        src.write_text('{"name": "ok", "etype": "single"}\n{"trunc')
        assert [e["name"] for e in load_events(str(src))] == ["ok"]


@pytest.fixture
def observability(tmp_path, monkeypatch):
    """Fresh global recorder (JSONL in tmp) + enabled tracer + reset
    registry, restored afterwards — the globals other suites also
    touch."""
    from veles_tpu.core import logger as logger_mod
    from veles_tpu.observe.metrics import get_metrics_registry
    from veles_tpu.observe.tracing import get_tracer

    events_path = str(tmp_path / "events.jsonl")
    recorder = EventRecorder()
    recorder.open(events_path)
    monkeypatch.setattr(logger_mod, "_event_recorder", recorder)
    tracer = get_tracer()
    registry = get_metrics_registry()
    was_traced, was_metered = tracer.enabled, registry.enabled
    tracer.enable()
    registry.reset()
    registry.enable()
    yield events_path
    recorder.close()
    tracer.enabled = was_traced
    registry.reset()
    registry.enabled = was_metered


def _walk_to_root(tree, span_id, stop_ids):
    seen = set()
    while True:
        assert span_id not in seen, "parent cycle at %s" % span_id
        seen.add(span_id)
        parent = tree.get(span_id, "missing")
        if parent is None or parent in stop_ids:
            return parent
        assert parent != "missing", \
            "span %s has a parent outside the tree" % span_id
        span_id = parent


class TestServingObservability:
    @pytest.fixture(scope="class")
    def model(self):
        from veles_tpu.parallel.transformer_step import (
            init_transformer_params)
        import jax.numpy as jnp

        rng = numpy.random.RandomState(0)
        heads, embed, vocab = 4, 16, 11
        params = init_transformer_params(rng, 2, embed, heads, vocab)
        table = jnp.asarray(
            rng.randn(vocab, embed).astype(numpy.float32) * 0.3)
        return params, table, heads, vocab

    def test_request_yields_connected_span_tree_and_metrics(
            self, model, observability, tmp_path):
        """The acceptance pair: one serving request produces ONE
        connected trace (admission -> prefill dispatch -> decode chunks
        -> collect) in the exported Chrome trace, and /metrics on the
        same surface exposes serving counters + decode histograms."""
        from veles_tpu.serving import GenerateAPI

        params, table, heads, vocab = model
        api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                          n_tokens=4, chunk=2, port=0)
        api.start()
        try:
            url = "http://127.0.0.1:%d" % api.port
            client_trace = "c0ffee01", "ab12"
            body, headers = post(
                url + "/generate", {"tokens": [1, 2, 3]},
                headers={"X-Veles-Trace": "%s/%s" % client_trace})
            assert len(body["tokens"]) == 4
            # the response echoes the request's trace id
            echoed = parse_trace_header(headers.get("X-Veles-Trace"))
            assert echoed is not None and echoed[0] == client_trace[0]
            metrics = get(url + "/metrics")
            assert ('veles_serving_requests_total{api="generate-api"'
                    ',outcome="completed"} 1') in metrics
            assert ('veles_serving_requests_total{api="generate-api"'
                    ',outcome="admitted"} 1') in metrics
            assert "veles_decode_dispatch_seconds_bucket" in metrics
            assert "veles_decode_admit_seconds_count" in metrics
            assert 'veles_decode_dispatches_total{kind="admit"} 1' \
                in metrics
        finally:
            api.stop()
        out = str(tmp_path / "trace.json")
        export_chrome_trace(observability, out)
        trace = json.loads(open(out).read())
        trees = span_tree(trace)
        # ONE trace: the client's id, continued through every layer.
        # The driver's own books between chunks (serve.drive_books,
        # serve.drive_idle) belong to no request: roots of their own
        drivers = {e["args"]["trace_id"] for e in trace["traceEvents"]
                   if e["name"].startswith("serve.drive_")}
        assert client_trace[0] not in drivers
        assert [t for t in trees if t not in drivers] \
            == [client_trace[0]], list(trees)
        tree = trees[client_trace[0]]
        names = {e["args"]["span_id"]: e["name"]
                 for e in trace["traceEvents"]
                 if e["args"].get("trace_id") == client_trace[0]}
        by_name = {}
        for span_id, name in names.items():
            by_name.setdefault(name, []).append(span_id)
        for required in ("serve.request", "serve.submit",
                         "decode.admit", "decode.dispatch",
                         "decode.collect", "serve.complete"):
            assert required in by_name, (required, sorted(by_name))
        # every span's parent chain terminates at the client's span —
        # one CONNECTED tree, no orphans
        stop = {client_trace[1]}
        for span_id in tree:
            assert _walk_to_root(tree, span_id, stop) in stop
        # the request span is the direct child of the client context
        for span_id in by_name["serve.request"]:
            assert tree[span_id] == client_trace[1]

    def test_run_record_says_the_slab_layout(self, model, observability,
                                             tmp_path):
        """Which device layout the K/V leaves lie in is said once per
        run: in ``/healthz`` and on the first traced ``decode.dispatch``
        span, as the state's own arrays report it."""
        from veles_tpu.parallel.decode import slot_layout_facts
        from veles_tpu.serving import GenerateAPI

        params, table, heads, vocab = model
        api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                          n_tokens=6, chunk=2, port=0)
        api.start()
        try:
            url = "http://127.0.0.1:%d" % api.port
            post(url + "/generate", {"tokens": [1, 2, 3]})
            health = json.loads(get(url + "/healthz"))
            facts = slot_layout_facts(api.decoder.state)
        finally:
            api.stop()
        assert health["kv_layout"] == facts
        for name in ("k", "v"):
            # (S, H·D, T): three dimensions, positions minor-most
            assert sorted(facts[name]["major_to_minor"]) == [0, 1, 2]
            assert facts[name]["major_to_minor"][-1] == 2
        slab = 2 * 2 * (2 * 16 * 32) * 4       # K, V; 2 blocks; f32
        assert facts["state_device_bytes"] >= slab
        out = str(tmp_path / "trace.json")
        export_chrome_trace(observability, out)
        dispatches = [e for e in json.loads(open(out).read())[
            "traceEvents"] if e["name"] == "decode.dispatch"]
        assert len(dispatches) >= 2
        said = [json.loads(e["args"]["kv_layout"]) for e in dispatches
                if "kv_layout" in e["args"]]
        assert said == [facts]                  # the first one only

    def test_metrics_expose_device_truth(self, observability):
        """The ISSUE acceptance: /metrics on GenerateAPI exposes
        compile-count, device-memory and MFU gauges — fed by real
        compiles of the slot programs and the driver's chunk cadence,
        not hand-planted samples. A DISTINCT model shape guarantees
        fresh compiles even when earlier suites warmed the jit caches
        for the shared toy model."""
        from veles_tpu.core.config import root
        from veles_tpu.observe.xla_stats import get_compile_tracker
        from veles_tpu.parallel.transformer_step import (
            init_transformer_params)
        from veles_tpu.serving import GenerateAPI
        import jax.numpy as jnp

        rng = numpy.random.RandomState(3)
        heads, embed, vocab = 2, 12, 13
        params = init_transformer_params(rng, 1, embed, heads, vocab)
        table = jnp.asarray(
            rng.randn(vocab, embed).astype(numpy.float32) * 0.3)
        tracker = get_compile_tracker()
        was_tracking = tracker.enabled
        tracker.reset()
        saved_peak = root.common.observe.get("peak_tflops", None)
        # CPU is not in the peak table; the override supplies the MFU
        # denominator (the knob unlisted devices use)
        root.common.observe.peak_tflops = 0.001
        api = GenerateAPI(params, table, heads, slots=2, max_len=64,
                          n_tokens=6, chunk=2, port=0)
        api.start()
        try:
            url = "http://127.0.0.1:%d" % api.port
            body, _ = post(url + "/generate", {"tokens": [1, 2, 3, 4]})
            assert len(body["tokens"]) == 6
            metrics = get(url + "/metrics")
            # compile counts per slot program
            assert 'veles_xla_compiles_total{program="decode.admit"}' \
                in metrics
            assert ('veles_xla_compiles_total'
                    '{program="decode.dispatch"}') in metrics
            assert "veles_xla_compile_seconds_total" in metrics
            # device memory (live-bytes fallback on CPU)
            assert "veles_device_memory_bytes" in metrics
            # online MFU: cost_analysis FLOPs over the chunk cadence
            assert ('veles_xla_program_flops'
                    '{program="decode.dispatch"}') in metrics
            assert 'veles_mfu_ratio{program="decode.dispatch"}' \
                in metrics
            assert "veles_device_peak_bf16_tflops 0.001" in metrics
        finally:
            api.stop()
            tracker.reset()
            tracker.enabled = was_tracking
            root.common.observe.peak_tflops = saved_peak

    def test_restful_api_mounts_metrics(self, observability):
        from veles_tpu.dummy import DummyWorkflow
        from veles_tpu.serving import RESTfulAPI

        api = RESTfulAPI(DummyWorkflow(), port=0, path="/api")
        api.feed = lambda data, request: None
        api.requests = []
        api.initialize()
        try:
            metrics = get("http://127.0.0.1:%d/metrics" % api.port)
            assert 'veles_serving_ready{api="restful-api"} 1' in metrics
        finally:
            api.stop()

    def test_web_status_mounts_metrics(self, observability):
        from veles_tpu.web_status import WebStatusServer

        server = WebStatusServer(port=0).start()
        try:
            metrics = get("http://127.0.0.1:%d/metrics" % server.port)
            assert "# TYPE" in metrics or metrics.strip() == ""
        finally:
            server.stop()

    def test_forge_mounts_metrics(self, observability, tmp_path):
        from veles_tpu.forge.server import ForgeServer

        server = ForgeServer(str(tmp_path / "store"), port=0).start()
        try:
            # exposition is live on the forge surface too
            get("http://127.0.0.1:%d/metrics" % server.port)
        finally:
            server.stop()


@pytest.mark.slow
class TestFleetObservability:
    def test_fleet_round_trip_metrics_and_trace(self, observability,
                                                tmp_path):
        """A real master+slave run: the master's /metrics sidecar
        aggregates fleet state incl. the slave's piggybacked counters,
        and one job reads master -> slave -> apply as a single
        connected trace."""
        from veles_tpu.core import prng
        from veles_tpu.core.config import root
        from veles_tpu.launcher import Launcher
        from veles_tpu.models.mlp import MLPWorkflow
        from sklearn.datasets import load_digits

        digits = load_digits()
        kw = dict(
            layers=(16, 10),
            loader_kwargs=dict(
                data=digits.data.astype(numpy.float32),
                labels=digits.target.astype(numpy.int32),
                class_lengths=[0, 297, 1500], minibatch_size=300,
                normalization_type="linear"),
            learning_rate=0.5, max_epochs=1)
        saved_port = root.common.observe.get("fleet_metrics_port", None)
        root.common.observe.fleet_metrics_port = 0
        try:
            prng.get("default").seed(42)
            prng.get("loader").seed(43)
            master = Launcher(listen_address="127.0.0.1:0")
            MLPWorkflow(master, name="fleet-obs", **kw)
            master.initialize()
            master_thread = threading.Thread(target=master.run,
                                             daemon=True)
            master_thread.start()
            prng.get("default").seed(42)
            prng.get("loader").seed(43)
            slave = Launcher(
                master_address="127.0.0.1:%d" % master.agent.port)
            MLPWorkflow(slave, name="fleet-obs", **kw)
            slave.initialize()
            slave_thread = threading.Thread(target=slave.run,
                                            daemon=True)
            slave_thread.start()
            deadline = time.time() + 120
            metrics_url = "http://127.0.0.1:%d/metrics" \
                % master.agent.metrics_port
            # poll mid-run until the slave's piggybacked rows show up
            piggybacked = ""
            while time.time() < deadline:
                try:
                    piggybacked = get(metrics_url, timeout=5)
                except OSError:
                    break  # master finished and closed the sidecar
                if 'slave="slave-1"' in piggybacked \
                        and "veles_fleet_jobs_total" in piggybacked:
                    break
                time.sleep(0.2)
            assert "veles_fleet_jobs_total" in piggybacked
            assert 'slave="slave-1"' in piggybacked, \
                piggybacked[-2000:]
            master_thread.join(timeout=120)
            slave_thread.join(timeout=120)
        finally:
            if saved_port is None:
                root.common.observe.fleet_metrics_port = None
            else:
                root.common.observe.fleet_metrics_port = saved_port
        events = load_events(observability)
        issues = [e for e in events if e.get("name") == "fleet.issue"]
        assert issues, "no fleet.issue events recorded"
        trace = chrome_trace(events)
        trees = span_tree(trace)
        jobs = {e["args"]["span_id"]: e for e in trace["traceEvents"]
                if e["name"] == "fleet.do_job"}
        applies = [e for e in trace["traceEvents"]
                   if e["name"] == "fleet.apply"]
        assert jobs and applies
        # every applied update chains master.issue -> slave.do_job ->
        # master.apply inside ONE trace
        verified = 0
        for apply_event in applies:
            args = apply_event["args"]
            parent = args.get("parent_id")
            if parent not in jobs:
                continue
            job = jobs[parent]
            assert job["args"]["trace_id"] == args["trace_id"]
            issue_id = job["args"].get("parent_id")
            issue = next(
                (e for e in trace["traceEvents"]
                 if e["args"].get("span_id") == issue_id), None)
            assert issue is not None and issue["name"] == "fleet.issue"
            assert issue["args"]["trace_id"] == args["trace_id"]
            verified += 1
        assert verified > 0
