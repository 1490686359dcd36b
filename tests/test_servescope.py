"""Serving goodput observatory: occupancy timelines, token-waste
decomposition, padding autopsy.

The tentpole suite (docs/observability.md "Serving goodput + slot
timeline"): unit tests for the accounting ring / record-path
discipline / the exact per-cause waste math against the real dense AND
paged engines, the wall decomposition, the slot occupancy timeline,
the detector-owned anomaly rules + incident artifacts naming the
dominant waste cause, the metrics/healthz/web-status surfaces, the
``observe serve-trace`` CLI (saved payload and --live), and the chaos
acceptance — a seeded waste profile must deterministically land an
incident naming EXACTLY the injected cause.

``make servescope`` runs this module standalone; the chaos end-to-end
rides the ``slow`` marker so tier-1 keeps its timeout margin.
"""

import json
import time
import urllib.request

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.observe import servescope
from veles_tpu.observe.history import (IncidentRecorder, MetricHistory,
                                       set_metric_history)
from veles_tpu.observe.metrics import MetricsRegistry
from veles_tpu.observe.servescope import (
    DISPATCH_RING_CAPACITY, OCCUPANCY_BREACH, OPEN_SLOT_CAP,
    SECOND_FIELDS, SECONDS_CAPACITY, SLOT_RING_CAPACITY, WASTE_CAUSES,
    WASTE_SHARE_BREACH, ServeScope, assemble_serve_trace,
    ensure_serve_registered, ensure_serve_rules, get_serve_scope,
    load_serve_payload, publish_serve_scope, serve_trace_main, watch_gc)
from veles_tpu.observe.trace_export import span_tree
from veles_tpu.parallel.decode import (admit_waste,
                                       page_overshoot_tokens,
                                       span_overshoot_tokens)

pytestmark = pytest.mark.servescope


@pytest.fixture(autouse=True)
def _fresh_scope():
    scope = get_serve_scope()
    scope.reset()
    scope.enabled = True
    yield scope
    scope.reset()


def _tiny(blocks=1, embed=32, heads=4, vocab=64):
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    table = jnp.asarray(
        rng.randn(vocab, embed).astype(numpy.float32) * 0.02)
    return params, table, heads


def _history(tmp_path, cooldown=0.0):
    return MetricHistory(
        registry=MetricsRegistry(enabled=False),
        incidents=IncidentRecorder(cooldown_s=cooldown,
                                   directory=str(tmp_path)))


# -- record-path discipline -------------------------------------------------

class TestRecordPath:
    def test_no_lock_attribute_anywhere(self):
        """The flight-recorder discipline: the scope may not hold a
        lock (the analyze gate's lock.record-path rule is the static
        twin of this runtime check)."""
        scope = ServeScope()
        for name, value in vars(scope).items():
            assert not hasattr(value, "acquire"), name
            assert "lock" not in name and "mutex" not in name

    def test_rings_bounded(self):
        scope = ServeScope()
        for index in range(DISPATCH_RING_CAPACITY + 500):
            scope.note_dispatch(2, 4, 2, 1, 0.0)
        assert len(scope._ring) == DISPATCH_RING_CAPACITY
        for rid in range(OPEN_SLOT_CAP + 100):
            scope.note_slot_admit(rid % 4, rid, "dense")
        assert len(scope._open) <= OPEN_SLOT_CAP
        for rid in range(SLOT_RING_CAPACITY + 200):
            scope.note_slot_admit(rid % 4, rid, "dense")
            scope.note_slot_retire(rid)
        assert len(scope._slots) == SLOT_RING_CAPACITY

    def test_disabled_is_noop(self):
        scope = ServeScope()
        scope.enabled = False
        scope.note_admit("dense", 16, 2, 2, 14, 18, 0, 0.001)
        scope.note_dispatch(2, 4, 2, 1, 0.0)
        scope.note_collect(4, 4, 0.0)
        scope.note_idle(0.1)
        scope.note_slot_admit(0, 0, "dense")
        scope.inject_waste("dead_slot", 100)
        assert scope.summary() is None
        assert sum(scope.waste.values()) == 0
        assert scope.seconds["idle"] == 0.0


# -- the waste math, helper-level then engine-level -------------------------

class TestWasteMath:
    def test_admit_waste_decomposition(self):
        assert admit_waste(16, [5, 9], 2) == (14, 18, 0)
        # 3 live rows padded to 4 -> one duplicate row of bucket size
        assert admit_waste(32, [17, 20, 30], 4) == (67, 29, 32)
        # a hit admission dispatches zero tokens
        assert admit_waste(0, [], 2) == (0, 0, 0)

    def test_span_overshoot_matches_brute_force(self):
        for lens, span, chunk in [([5, 9], 24, 2), ([5], 8, 4),
                                  ([7, 7, 7], 16, 8), ([15], 16, 4),
                                  ([3], 64, 1), ([63], 64, 8)]:
            expected = sum(
                max(0, span - (n + i))
                for n in lens for i in range(1, chunk + 1))
            assert span_overshoot_tokens(lens, span, chunk) \
                == expected, (lens, span, chunk)

    def test_page_overshoot_is_the_span_form(self):
        assert page_overshoot_tokens([5], 2, 8, 1) \
            == span_overshoot_tokens([5], 16, 1)

    def test_dense_engine_exact_accounting(self, _fresh_scope):
        """Two prompts (lens 5 and 9, one bucket-16 group), budget 4,
        4 slots, tile 8, unpipelined chunk=1 drain: every cause is
        hand-computable."""
        from veles_tpu.serving import ContinuousDecoder

        scope = _fresh_scope
        params, table, heads = _tiny()
        dec = ContinuousDecoder(params, table, heads, slots=4,
                                max_len=64, n_tokens=4, tile=8)
        dec.submit([1, 2, 3, 4, 5])
        dec.submit(list(range(1, 10)))
        dec.run_until_drained(chunk=1)
        assert scope.useful == {"prefill": 14, "decode": 8}
        assert scope.waste["bucket_pad"] == 18     # (16-5) + (16-9)
        assert scope.waste["group_dup"] == 0       # 2 rows is pow2
        assert scope.waste["dead_slot"] == 8       # 2 idle lanes x 4
        assert scope.waste["discard"] == 0         # chunk=1, no tails
        assert scope.waste["page_overshoot"] == 0
        expected = 0
        lens = [5, 9]
        for _ in range(4):
            span = -(-(max(lens) + 1) // 8) * 8
            expected += sum(span - (n + 1) for n in lens)
            lens = [n + 1 for n in lens]
        assert scope.waste["span_overshoot"] == expected
        occupancy = scope.occupancy()
        assert occupancy["fraction"] == 0.5        # 2 of 4 lanes live
        assert occupancy["total_lane_steps"] == 16

    def test_group_duplicate_rows_counted(self, _fresh_scope):
        """Three same-bucket prompts pad to a 4-row group: one
        duplicate row of bucket positions books as group_dup."""
        from veles_tpu.serving import ContinuousDecoder

        scope = _fresh_scope
        params, table, heads = _tiny()
        dec = ContinuousDecoder(params, table, heads, slots=4,
                                max_len=64, n_tokens=1, tile=8)
        for _ in range(3):
            dec.submit([1, 2, 3])
        dec.run_until_drained(chunk=1)
        assert scope.waste["group_dup"] == 16
        assert scope.useful["prefill"] == 9
        assert scope.waste["bucket_pad"] == 3 * (16 - 3)

    def test_paged_engine_exact_accounting(self, _fresh_scope):
        """The paged twin: PB-page gathers overshoot the live length,
        dead lanes' scratch appends book as dead_slot."""
        from veles_tpu.serving import ContinuousDecoder

        scope = _fresh_scope
        params, table, heads = _tiny()
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=64, n_tokens=3, tile=8,
                                paged=True, page_size=8)
        dec.submit([1, 2, 3])
        dec.run_until_drained(chunk=1)
        assert scope.useful == {"prefill": 3, "decode": 3}
        assert scope.waste["bucket_pad"] == 13     # bucket 16 - 3
        assert scope.waste["dead_slot"] == 3       # 1 idle lane x 3
        # steps gather 1 page (8 positions) at lens 3/4/5 ->
        # overshoot 4 + 3 + 2
        assert scope.waste["page_overshoot"] == 9
        assert scope.waste["span_overshoot"] == 0
        rows = scope.slot_rows()
        assert [row["kind"] for row in rows] == ["cold"]

    def test_lag_tail_books_discard(self, _fresh_scope):
        """The pipelined drain's lag-1 retirement tail: tokens
        computed for a finished slot are discarded, never delivered —
        and the useful tally still equals exactly what was
        delivered."""
        from veles_tpu.serving import ContinuousDecoder

        scope = _fresh_scope
        params, table, heads = _tiny()
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=64, n_tokens=5, tile=8)
        dec.submit([1, 2, 3])
        dec.submit([4, 5, 6])
        results = dec.drain_pipelined(chunk=2)
        delivered = sum(len(tokens) for tokens in results.values())
        assert delivered == 10
        assert scope.useful["decode"] == delivered
        assert scope.waste["discard"] > 0

    def test_cancel_retires_slot_as_cancelled(self, _fresh_scope):
        from veles_tpu.serving import ContinuousDecoder

        scope = _fresh_scope
        params, table, heads = _tiny()
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=64, n_tokens=8, tile=8)
        rid = dec.submit([1, 2, 3])
        dec.step()
        assert dec.cancel(rid)
        rows = [row for row in scope.slot_rows()
                if row["rid"] == rid]
        assert rows and rows[0]["reason"] == "cancelled"
        assert rows[0]["retire"] is not None

    def test_injected_waste_books_named_cause(self, _fresh_scope):
        scope = _fresh_scope
        scope.inject_waste("span_overshoot", 123)
        scope.inject_waste("not-a-cause", 999)  # silently ignored
        assert scope.waste["span_overshoot"] == 123
        assert sum(scope.waste.values()) == 123
        assert scope.dominant_cause() == "span_overshoot"


# -- wall decomposition + the slot occupancy timeline -----------------------

class TestWallAndTimeline:
    def test_wall_components_accumulate(self):
        scope = ServeScope()
        base = time.monotonic()
        scope.note_admit("dense", 16, 1, 1, 5, 11, 0, 0.010,
                         now=base + 0.010)
        scope.note_dispatch(2, 4, 1, 0, 0.020, now=base + 0.040)
        scope.note_collect(2, 2, 0.005, now=base + 0.050)
        scope.note_idle(0.030, now=base + 0.080)
        seconds = scope.seconds
        assert seconds["prefill_compute"] == pytest.approx(0.010)
        assert seconds["decode_compute"] == pytest.approx(0.025)
        # dispatch started 10ms after the admit mark, collect started
        # 5ms after the dispatch mark -> 15ms of host bookkeeping
        assert seconds["host"] == pytest.approx(0.015)
        assert seconds["idle"] == pytest.approx(0.030)

    def test_slot_timeline_ordering(self, _fresh_scope):
        from veles_tpu.serving import ContinuousDecoder

        scope = _fresh_scope
        params, table, heads = _tiny()
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=64, n_tokens=3, tile=8)
        first = dec.submit([1, 2, 3])
        second = dec.submit([4, 5, 6, 7, 8])
        dec.run_until_drained(chunk=1)
        rows = {row["rid"]: row for row in scope.slot_rows()}
        assert set(rows) == {first, second}
        for row in rows.values():
            assert row["kind"] == "dense"
            assert row["reason"] == "done"
            assert row["admit"] <= row["first"] <= row["retire"]
            assert row["slot"] in (0, 1)


# -- detector-owned anomaly rules + incident artifacts ----------------------

class TestAutopsy:
    def test_waste_incident_names_dominant_cause(self, tmp_path):
        scope = ServeScope()
        history = _history(tmp_path)
        path = None
        for _ in range(4):
            scope.note_collect(8, 8, 0.0)
            scope.inject_waste("group_dup", 5000)
            scope.inject_waste("bucket_pad", 7)
            path = scope.autopsy_tick(history) or path
        assert path is not None
        doc = json.load(open(path))
        assert doc["reason"] == "serve_waste"
        assert doc["trigger"]["dominant_cause"] == "group_dup"
        assert doc["trigger"]["value"] >= WASTE_SHARE_BREACH
        assert ["cause", "group_dup"] in doc["trigger"]["labels"]
        # the breach-window per-cause decomposition rides the artifact
        assert doc["trigger"]["waste_window"]["group_dup"] > 0

    def test_occupancy_collapse_incident(self, tmp_path):
        scope = ServeScope()
        history = _history(tmp_path)
        path = None
        for _ in range(5):
            scope.note_dispatch(4, 8, 1, 0, 0.0)  # 1/8 occupancy
            scope.note_collect(4, 4, 0.0)
            # keep the waste share healthy so only occupancy breaches
            scope.useful["decode"] += 1000
            path = scope.autopsy_tick(history) or path
        assert path is not None and "serve_occupancy" in path
        doc = json.load(open(path))
        assert doc["trigger"]["value"] <= OCCUPANCY_BREACH

    def test_rules_are_external_and_idempotent(self, tmp_path):
        history = _history(tmp_path)
        waste, occupancy = ensure_serve_rules(history)
        assert waste.external and occupancy.external
        assert ensure_serve_rules(history) == (waste, occupancy)
        # the sampler-side evaluator must skip detector-owned rules
        history.sample(rows=[("veles_serve_waste_share", "gauge", (),
                              0.99)])
        assert waste.streak == 0 and waste.fired_total == 0

    def test_healthy_window_resets_streak(self, tmp_path):
        scope = ServeScope()
        history = _history(tmp_path)
        waste, _ = ensure_serve_rules(history)
        scope.inject_waste("dead_slot", 1000)
        scope.autopsy_tick(history)
        assert waste.streak == 1
        scope.useful["decode"] += 10000
        scope.autopsy_tick(history)
        assert waste.streak == 0 and waste.breach_since is None

    def test_toy_trickle_below_floor_never_pages(self, tmp_path):
        """The verify-drive regression: a lightly-loaded server's
        organic dead-slot/overshoot waste on a handful of tokens must
        not land incidents — sub-floor windows accumulate instead of
        judging."""
        from veles_tpu.observe.servescope import MIN_EVAL_TOKENS

        scope = ServeScope()
        history = _history(tmp_path)
        waste, _ = ensure_serve_rules(history)
        for _ in range(20):
            scope.note_dispatch(2, 4, 1, 3, 0.0)   # mostly waste
            scope.note_collect(2, 2, 0.0)
            assert scope.autopsy_tick(history) is None
        assert waste.fired_total == 0
        # ... but the accumulated trickle IS judged once it crosses
        # the floor (anchors were never consumed)
        scope.inject_waste("dead_slot", MIN_EVAL_TOKENS)
        scope.autopsy_tick(history)
        assert waste.streak >= 1

    def test_dispatch_free_window_with_stale_streak(self, tmp_path):
        """Review regression: an admit-only evaluation window
        (occupancy None) meeting a COMPLETED occupancy streak from
        earlier windows must not fire (or crash formatting None) —
        the streak simply holds until decode traffic returns."""
        scope = ServeScope()
        history = _history(tmp_path)
        waste_rule, occupancy_rule = ensure_serve_rules(history)
        # build the occupancy streak while the waste rule (which
        # fires first) burns its cooldown
        for _ in range(3):
            scope.note_dispatch(4, 8, 1, 0, 0.0)
            scope.note_collect(4, 4, 0.0)
            scope.useful["decode"] += 1000
            scope.autopsy_tick(history)
        assert occupancy_rule.streak >= occupancy_rule.for_samples
        occupancy_rule.last_fired = None  # armed to fire next breach
        # a dispatch-free window: prefill tokens only, occupancy None
        scope.note_admit("dense", 512, 1, 1, 400, 112, 0, 0.0)
        assert scope.autopsy_tick(history) is None
        # the armed rule did NOT fire on the None window
        assert occupancy_rule.last_fired is None

    def test_no_traffic_is_a_noop(self, tmp_path):
        scope = ServeScope()
        history = _history(tmp_path)
        assert scope.autopsy_tick(history) is None
        assert scope.autopsy_tick(None) is None

    def test_cooldown_limits_artifacts(self, tmp_path):
        scope = ServeScope()
        history = MetricHistory(
            registry=MetricsRegistry(enabled=False),
            incidents=IncidentRecorder(cooldown_s=3600.0,
                                       directory=str(tmp_path)))
        paths = []
        for _ in range(6):
            scope.note_collect(2, 2, 0.0)
            scope.inject_waste("dead_slot", 500)
            result = scope.autopsy_tick(history)
            if result:
                paths.append(result)
        assert len(paths) == 1


# -- metrics + health surfaces ----------------------------------------------

class TestMetricsAndHealth:
    def test_collector_publishes_families(self, _fresh_scope):
        scope = _fresh_scope
        scope.note_admit("dense", 16, 2, 2, 14, 18, 0, 0.001)
        scope.note_dispatch(2, 4, 2, 3, 0.001)
        scope.note_collect(4, 4, 0.0)
        registry = MetricsRegistry(enabled=True)
        ensure_serve_registered(registry)
        ensure_serve_registered(registry)  # idempotent
        text = registry.expose()
        for token in ("veles_serve_goodput_fraction",
                      'veles_serve_goodput_seconds_total{'
                      'component="prefill_compute"}',
                      'veles_serve_token_waste_total{'
                      'cause="bucket_pad"}',
                      'veles_serve_tokens_useful_total{'
                      'phase="decode"}',
                      "veles_serve_slot_occupancy",
                      "veles_serve_waste_share"):
            assert token in text, token

    def test_trafficless_scope_publishes_nothing(self):
        registry = MetricsRegistry(enabled=True)
        publish_serve_scope(registry, ServeScope())
        assert "veles_serve_" not in registry.expose()

    def test_health_snapshot_and_dashboard_cell(self, _fresh_scope):
        from veles_tpu.serving import ServingHealth
        from veles_tpu.web_status import format_serving_health

        scope = _fresh_scope
        scope.note_dispatch(4, 4, 2, 0, 0.0)
        scope.note_collect(8, 8, 0.0)
        health = ServingHealth()
        health.attach_servescope(scope)
        snap = health.snapshot()
        # 8 live of 16 lane-steps; 8 useful tokens vs 8 dead-slot
        assert snap["servescope"]["occupancy"] == 0.5
        assert snap["servescope"]["goodput"] == 0.5
        assert snap["servescope"]["dominant_cause"] == "dead_slot"
        cell = format_serving_health(snap)
        assert "occupancy 50%" in cell
        assert "goodput 50%" in cell
        assert "waste 50% (dead_slot)" in cell

    def test_waste_causes_cover_the_catalog(self):
        assert set(WASTE_CAUSES) == {
            "bucket_pad", "group_dup", "span_overshoot",
            "page_overshoot", "tile_pad", "dead_slot", "discard"}


# -- the per-second books ---------------------------------------------------

def _summed(scope):
    """Each field of the per-second books summed over every second."""
    rows = scope.second_rows().values()
    return {field: sum(row[field] for row in rows)
            for field in SECOND_FIELDS}


class TestPerSecondBooks:
    @pytest.mark.parametrize("drain", ["pipelined", "steps"])
    def test_rows_sum_to_the_cumulative_books(self, _fresh_scope, drain):
        """A dense decoder's admissions, lane-steps and delivered
        tokens, booked by wall second, add up to the scope's own
        cumulative tallies whichever way the decoder is drained."""
        from veles_tpu.serving import ContinuousDecoder

        scope = _fresh_scope
        params, table, heads = _tiny()
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=64, n_tokens=5, tile=8)
        for prompt in ([1, 2, 3], [4, 5, 6, 7], list(range(1, 20)),
                       [9]):
            dec.submit(prompt)
        if drain == "pipelined":
            delivered = sum(len(tokens) for tokens
                            in dec.drain_pipelined(chunk=2).values())
        else:
            delivered = sum(len(tokens) for tokens
                            in dec.run_until_drained(chunk=1).values())
        books = _summed(scope)
        assert books["admits"] == scope.admits
        assert books["admitted"] == dec.dispatch_counts[
            "admit_requests"] == 4
        assert books["admit_rows"] >= books["admitted"]
        assert books["lane_steps"] == scope.total_lane_steps
        assert books["live_lane_steps"] == scope.live_lane_steps
        assert books["delivered"] == scope.useful["decode"] \
            == delivered == 20
        assert books["admit_ms"] > 0 and books["dispatch_ms"] > 0
        assert all(value >= 0 for value in books.values())

    def test_milliseconds_land_by_what_the_driver_did(self):
        scope = ServeScope()
        base = time.monotonic()
        scope.note_admit("dense", 16, 1, 1, 5, 11, 0, 0.010,
                         now=base + 0.010)
        scope.note_dispatch(2, 4, 1, 0, 0.020, now=base + 0.040)
        scope.note_collect(2, 2, 0.005, now=base + 0.050)
        scope.note_dispatch(2, 4, 1, 0, 0.002, now=base + 0.070)
        scope.note_idle(0.030, now=base + 0.100)
        books = _summed(scope)
        assert books["admit_ms"] == pytest.approx(10.0, abs=0.02)
        assert books["dispatch_ms"] == pytest.approx(22.0, abs=0.02)
        assert books["device_wait_ms"] == pytest.approx(5.0, abs=0.02)
        # 10 ms admit -> dispatch, 5 ms dispatch -> collect, 18 ms
        # collect -> the second dispatch
        assert books["host_ms"] == pytest.approx(33.0, abs=0.02)
        assert books["idle_ms"] == pytest.approx(30.0, abs=0.02)
        assert max(row["worst_pass_ms"] for row
                   in scope.second_rows().values()) \
            == pytest.approx(18.0, abs=0.02)
        assert books["lane_steps"] == 16 and books["live_lane_steps"] == 4

    def test_a_note_costs_microseconds_on_and_a_check_off(self):
        """The record path stays cheap: a note with the books on is a
        few microseconds (about 1.9 on a TPU v5e host), one enabled
        check when off; the bounds are loose for a loaded test host
        and catch I/O or a scan of the ring, not a microsecond."""
        def per_note(scope, passes=2000):
            t0 = time.perf_counter()
            for _ in range(passes):
                scope.note_admit("dense", 256, 2, 2, 400, 112, 0, 0.0176)
                scope.note_dispatch(8, 16, 15, 0, 0.0004, kernel=True)
                scope.note_collect(120, 112, 0.012)
                scope.note_idle(0.0)
            return (time.perf_counter() - t0) / (4 * passes) * 1e6

        on, off = ServeScope(), ServeScope()
        off.enabled = False
        assert min(per_note(on) for _ in range(3)) < 50.0
        assert min(per_note(off) for _ in range(3)) < 5.0
        assert off.second_rows() == {} and len(on.second_rows()) >= 1

    def test_a_forced_collection_books_into_its_second(self, _fresh_scope):
        import gc

        watch_gc()
        watch_gc()  # one hook a process
        assert gc.callbacks.count(servescope._on_gc) == 1
        before = int(time.time())
        gc.collect()
        after = int(time.time())
        rows = [row for second, row in
                _fresh_scope.second_rows().items()
                if before <= int(second) <= after]
        assert sum(row["gc_ms"] for row in rows) > 0
        assert sum(row["gc_count"] for row in rows) >= 1

    def test_a_collection_is_annotated_while_a_capture_is_on(
            self, _fresh_scope, monkeypatch):
        import gc

        from veles_tpu.observe.tracing import get_tracer

        annotated = []

        class Annotation:
            def __init__(self, name):
                annotated.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        watch_gc()
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
        tracer = get_tracer()
        monkeypatch.setattr(tracer, "annotate_device", True)
        gc.collect()
        monkeypatch.setattr(tracer, "annotate_device", False)
        gc.collect()
        assert annotated == ["host.gc"]

    def test_a_disabled_scope_times_no_collection(self, _fresh_scope):
        import gc

        watch_gc()
        _fresh_scope.enabled = False
        try:
            gc.collect()
        finally:
            _fresh_scope.enabled = True
        assert _fresh_scope.second_rows() == {}

    def test_collections_in_other_threads_lose_no_booking(
            self, _fresh_scope):
        """The driver books its notes while threads of its own
        collect: no update is lost on either side (one writer a field,
        rows made with ``setdefault``), under a switch interval short
        enough to interleave every bytecode."""
        import gc
        import sys
        import threading

        scope = _fresh_scope
        watch_gc()
        stops = []

        def count(phase, info):
            if phase == "stop":
                stops.append(1)

        def collect():
            for _ in range(200):
                gc.collect(0)

        interval = sys.getswitchinterval()
        gc.callbacks.append(count)
        threads = [threading.Thread(target=collect) for _ in range(16)]
        try:
            sys.setswitchinterval(1e-6)
            for thread in threads:
                thread.start()
            for _ in range(2000):
                scope.note_dispatch(8, 16, 12, 0, 0.0)
                scope.note_collect(96, 90, 0.0)
            for thread in threads:
                thread.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
            gc.callbacks.remove(count)
        assert not any(thread.is_alive() for thread in threads)
        books = _summed(scope)
        assert books["lane_steps"] == 2000 * 128
        assert books["live_lane_steps"] == 2000 * 96
        assert books["delivered"] == 2000 * 90
        assert books["gc_count"] == len(stops) > 0

    def test_the_ring_drops_its_oldest_second_at_its_cap(self,
                                                         monkeypatch):
        scope = ServeScope()
        clock = [1_700_000_000.5]
        monkeypatch.setattr(servescope.time, "time", lambda: clock[0])
        for _ in range(SECONDS_CAPACITY + 3):
            scope.note_idle(0.001)
            clock[0] += 1.0
        seconds = [int(second) for second in scope.second_rows()]
        assert len(seconds) == SECONDS_CAPACITY
        assert seconds[0] == 1_700_000_003
        assert seconds[-1] == 1_700_000_000 + SECONDS_CAPACITY + 2
        assert scope.second_rows()[str(seconds[-1])]["idle_ms"] \
            == pytest.approx(1.0)

    def test_healthz_carries_the_books_after_one_request(
            self, _fresh_scope):
        from veles_tpu.serving import GenerateAPI

        params, table, heads = _tiny()
        api = GenerateAPI(params, table, heads, slots=2, max_len=64,
                          n_tokens=3, chunk=2, chaos=None).start()
        try:
            url = "http://127.0.0.1:%d" % api.port
            request = urllib.request.Request(
                url + "/generate",
                json.dumps({"tokens": [1, 2, 3]}).encode(),
                {"Content-Type": "application/json"})
            urllib.request.urlopen(request, timeout=30).read()
            healthz = json.load(urllib.request.urlopen(
                url + "/healthz", timeout=10))
            metrics = urllib.request.urlopen(
                url + "/metrics", timeout=10).read().decode()
        finally:
            api.stop()
        books = healthz["counters"]["serve_seconds"]
        assert books
        for second, row in books.items():
            assert int(second) > 1_600_000_000
            assert set(row) == set(SECOND_FIELDS)
        assert sum(row["delivered"] for row in books.values()) == 3
        assert sum(row["admitted"] for row in books.values()) == 1
        # /metrics publishes the request outcomes, not the books
        assert "serve_seconds" not in metrics
        assert 'outcome="completed"' in metrics


# -- trace assembly + the serve-trace CLI -----------------------------------

def _payload():
    return {
        "kind": "servescope", "schema": 1, "pid": 7,
        "goodput": {"fraction": 0.5, "useful_tokens": 10,
                    "waste_tokens": 10, "seconds": {}},
        "waste": {"dead_slot": 10}, "dominant_cause": "dead_slot",
        "occupancy": {"fraction": 0.5, "live_lane_steps": 1,
                      "total_lane_steps": 2},
        "slots": [
            {"slot": 0, "rid": 7, "kind": "dense", "admit": 1.0,
             "first": 1.1, "retire": 1.5, "reason": "done",
             "trace": None, "span": None},
            {"slot": 1, "rid": 8, "kind": "hit", "admit": 1.2,
             "first": None, "retire": None, "reason": None,
             "trace": "abc", "span": "s1"}],
        "requests": {"inflight": [], "slowest": [
            {"rid": 7, "id": 3, "trace": "t7",
             "outcome": "completed",
             "stages": [["staged", 0.9], ["admitted", 1.0],
                        ["resolved", 1.5]]}]},
    }


class TestServeTrace:
    def test_one_row_per_slot_and_connected_chains(self):
        trace = assemble_serve_trace(_payload())
        events = trace["traceEvents"]
        slots_pid = next(
            e["pid"] for e in events
            if e.get("ph") == "M" and e["name"] == "process_name"
            and e["args"]["name"].startswith("slots"))
        slot_tids = {e["tid"] for e in events
                     if e.get("ph") == "M"
                     and e["name"] == "thread_name"
                     and e["pid"] == slots_pid}
        assert slot_tids == {0, 1}
        trees = span_tree(trace)
        # the occupancy span parents to the ledger-row span: one
        # connected chain per request, linked by the trace id
        assert trees["t7"]["occ-7"] == "req-7"
        assert "req-7" in trees["t7"]
        assert trees["t7"]["first-7"] == "occ-7"
        # the still-open slot renders (no retire -> a B event)
        assert any(e.get("ph") == "B" for e in events)

    def test_cli_round_trip_saved_payload(self, tmp_path, capsys):
        saved = tmp_path / "serve.json"
        saved.write_text(json.dumps(_payload()))
        assert serve_trace_main(str(saved)) == 0
        out = capsys.readouterr().out
        assert "dominant waste cause: dead_slot" in out
        trace_path = tmp_path / "serve.trace.json"
        assert trace_path.exists()
        trace = json.loads(trace_path.read_text())
        assert trace["traceEvents"]

    def test_cli_rejects_garbage(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"kind": "nope"}))
        assert serve_trace_main(str(bad)) == 1
        missing = tmp_path / "missing.json"
        assert serve_trace_main(str(missing)) == 1

    def test_load_payload_unwraps_embedding(self, tmp_path):
        wrapped = tmp_path / "wrapped.json"
        wrapped.write_text(json.dumps({"servescope": _payload()}))
        assert load_serve_payload(str(wrapped))["kind"] == "servescope"


# -- HTTP surfaces (GenerateAPI end to end) ---------------------------------

class TestHTTPSurfaces:
    def test_debug_serve_index_metrics_and_live_trace(
            self, _fresh_scope, tmp_path):
        from veles_tpu.serving import GenerateAPI

        params, table, heads = _tiny()
        api = GenerateAPI(params, table, heads, slots=2, max_len=64,
                          n_tokens=3, chunk=2, chaos=None).start()
        try:
            url = "http://127.0.0.1:%d" % api.port
            request = urllib.request.Request(
                url + "/generate",
                json.dumps({"tokens": [1, 2, 3]}).encode(),
                {"Content-Type": "application/json"})
            reply = json.load(urllib.request.urlopen(request,
                                                     timeout=30))
            assert len(reply["tokens"]) == 3
            debug = json.load(urllib.request.urlopen(
                url + "/debug/serve", timeout=10))
            assert debug["kind"] == "servescope"
            assert debug["goodput"]["useful_tokens"] > 0
            assert any(row["reason"] == "done"
                       for row in debug["slots"])
            assert "requests" in debug
            index = json.load(urllib.request.urlopen(
                url + "/debug/", timeout=10))
            assert set(index["surfaces"]) == {
                "/debug/requests", "/debug/history", "/debug/serve",
                "/debug/memory"}
            healthz = json.load(urllib.request.urlopen(
                url + "/healthz", timeout=10))
            assert 0.0 <= healthz["servescope"]["goodput"] <= 1.0
            assert "occupancy" in healthz["servescope"]
            metrics = urllib.request.urlopen(
                url + "/metrics", timeout=10).read().decode()
            assert "veles_serve_goodput_fraction" in metrics
            assert 'veles_serve_token_waste_total{cause="dead_slot"}' \
                in metrics
            out = tmp_path / "live.trace.json"
            assert serve_trace_main(live=url, output=str(out)) == 0
            trace = json.loads(out.read_text())
            assert trace["traceEvents"]
        finally:
            api.stop()

    def test_restful_api_mounts_index(self):
        from veles_tpu.core.httpd import DEBUG_SURFACES
        assert set(DEBUG_SURFACES) == {
            "/debug/requests", "/debug/history", "/debug/serve",
            "/debug/memory"}


# -- the chaos waste profile ------------------------------------------------

class TestChaosWasteProfile:
    def test_config_validation(self):
        from veles_tpu.serving_chaos import ServingChaosConfig

        with pytest.raises(ValueError, match="waste_cause"):
            ServingChaosConfig(waste_cause="nope", waste_tokens=10,
                               waste_steps=2)
        with pytest.raises(ValueError):
            ServingChaosConfig(waste_cause="dead_slot",
                               waste_tokens=-1)
        config = ServingChaosConfig(waste_cause="group_dup",
                                    waste_tokens=1000, waste_at=1,
                                    waste_steps=4)
        assert config.any_profile
        assert config.expected_leading_cause() == "group_dup"
        assert config.expected_leading_series()["waste_profile"] \
            == "veles_serve_waste_share"
        assert ServingChaosConfig().expected_leading_cause() is None

    @pytest.mark.slow
    def test_injected_cause_names_itself(self, _fresh_scope,
                                         tmp_path):
        """The acceptance: a seeded chaos waste profile deterministically
        yields an incident artifact naming the injected dominant
        cause."""
        from veles_tpu.serving import GenerateAPI
        from veles_tpu.serving_chaos import (ServingChaosConfig,
                                             ServingChaosMonkey)

        config = ServingChaosConfig(waste_cause="group_dup",
                                    waste_tokens=5000, waste_at=1,
                                    waste_steps=6)
        monkey = ServingChaosMonkey(config)
        history = _history(tmp_path)
        set_metric_history(history)
        params, table, heads = _tiny()
        api = GenerateAPI(params, table, heads, slots=4, max_len=64,
                          n_tokens=4, chunk=2, chaos=monkey).start()
        try:
            url = "http://127.0.0.1:%d" % api.port
            for prompt in ([1, 2, 3], list(range(1, 10))):
                request = urllib.request.Request(
                    url + "/generate",
                    json.dumps({"tokens": prompt}).encode(),
                    {"Content-Type": "application/json"})
                json.load(urllib.request.urlopen(request, timeout=30))
            def waste_incidents():
                return sorted(tmp_path.glob(
                    "incident-*-serve_waste-*.json"))

            deadline = time.monotonic() + 20
            while not waste_incidents() \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
        finally:
            api.stop()
            set_metric_history(None)
        assert monkey.counters["waste_injections"] > 0
        # the synthetic injection also craters occupancy, so a
        # serve_occupancy incident may land too — the acceptance is
        # the WASTE incident naming the injected cause
        paths = waste_incidents()
        assert paths
        doc = json.load(open(paths[0]))
        assert doc["reason"] == "serve_waste"
        assert doc["trigger"]["dominant_cause"] \
            == config.expected_leading_cause()
        # the scope's own decomposition agrees (the injected cause
        # dominates the organic padding/overshoot waste)
        assert _fresh_scope.dominant_cause() == "group_dup"
