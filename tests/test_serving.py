"""Tests for REST serving, interactive loader, and web status (reference
test_restful.py / test_web_status.py roles)."""

import json
import os
import threading
import urllib.request
import urllib.error

import numpy
import pytest

from veles_tpu.dummy import DummyWorkflow
from veles_tpu.serving import InteractiveLoader, RESTfulAPI, RestfulLoader
from veles_tpu.web_status import StatusNotifier, WebStatusServer


def post(url, payload, timeout=10):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


class ServingHarness:
    """loader -> double(input) -> api loop on a background thread."""

    def __init__(self, mb=4, max_response_time=0.05):
        wf = DummyWorkflow()
        self.loader = RestfulLoader(wf, sample_shape=(3,),
                                    minibatch_size=mb,
                                    max_response_time=max_response_time)
        self.loader.initialize()
        self.api = RESTfulAPI(wf, port=0, path="/api")
        self.api.feed = self.loader.feed
        self.api.requests = []
        self.api.initialize()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop.is_set():
            self.loader.run()
            if self.loader.complete:
                return
            batch = numpy.asarray(self.loader.minibatch_data.mem)
            self.api.results = batch * 2.0
            self.api.requests = self.loader.requests
            self.api.run()

    @property
    def url(self):
        return "http://127.0.0.1:%d/api" % self.api.port

    def close(self):
        self._stop.set()
        self.loader.stop()
        self.api.stop()


@pytest.fixture
def harness():
    h = ServingHarness()
    yield h
    h.close()


class TestRESTfulAPI:
    def test_list_codec(self, harness):
        out = post(harness.url, {"input": [1.0, 2.0, 3.0],
                                 "codec": "list"})
        assert out["result"] == [2.0, 4.0, 6.0]

    def test_base64_codec(self, harness):
        import base64
        arr = numpy.array([0.5, 1.5, 2.5], numpy.float32)
        out = post(harness.url, {
            "input": base64.b64encode(arr.tobytes()).decode(),
            "codec": "base64", "shape": [3], "type": "float32"})
        assert out["result"] == [1.0, 3.0, 5.0]

    def test_concurrent_requests_batched(self, harness):
        results = {}

        def call(i):
            results[i] = post(harness.url,
                              {"input": [float(i)] * 3, "codec": "list"})

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=15)
        for i in range(3):
            assert results[i]["result"] == [2.0 * i] * 3

    def test_bad_requests(self, harness):
        for payload in ({"input": [1, 2, 3]},  # no codec
                        {"codec": "list"},  # no input
                        {"input": "x", "codec": "bogus"}):
            with pytest.raises(urllib.error.HTTPError) as err:
                post(harness.url, payload)
            assert err.value.code == 400

    def test_base64_needs_shape_and_type(self, harness):
        with pytest.raises(urllib.error.HTTPError) as err:
            post(harness.url, {"input": "QUFB", "codec": "base64"})
        assert err.value.code == 400

    def test_ragged_list_input_gets_400(self, harness):
        # regression: ragged arrays must 400, not drop the connection
        with pytest.raises(urllib.error.HTTPError) as err:
            post(harness.url, {"input": [[1], [2, 3]], "codec": "list"})
        assert err.value.code == 400

    def test_zero_max_response_time_still_flushes(self):
        # regression: max_response_time=0 meant "wait forever"
        h = ServingHarness(mb=4, max_response_time=0)
        try:
            out = post(h.url, {"input": [1.0, 1.0, 1.0], "codec": "list"},
                       timeout=15)
            assert out["result"] == [2.0, 2.0, 2.0]
        finally:
            h.close()


class TestInteractiveLoader:
    def test_feed_and_complete(self):
        loader = InteractiveLoader(DummyWorkflow(), sample_shape=(4,))
        loader.initialize()
        served = []

        def run_once():
            loader.run()
            served.append(numpy.asarray(loader.minibatch_data.mem).copy())

        t = threading.Thread(target=run_once)
        t.start()
        loader.feed(numpy.arange(4.0))
        t.join(timeout=10)
        assert not t.is_alive()
        numpy.testing.assert_array_equal(served[0][0],
                                         [0.0, 1.0, 2.0, 3.0])
        loader.feed(None)
        assert bool(loader.complete)

    def test_feed_from_npy(self, tmp_path):
        path = str(tmp_path / "x.npy")
        numpy.save(path, numpy.ones(4, numpy.float32))
        loader = InteractiveLoader(DummyWorkflow(), sample_shape=(4,))
        loader.initialize()
        t = threading.Thread(target=loader.run)
        t.start()
        loader.feed(path)
        t.join(timeout=10)
        numpy.testing.assert_array_equal(
            numpy.asarray(loader.minibatch_data.mem)[0], numpy.ones(4))


class TestWebStatus:
    @pytest.fixture
    def server(self, tmp_path):
        srv = WebStatusServer(port=0, plots_directory=str(tmp_path))
        srv.start()
        yield srv, tmp_path
        srv.stop()

    def test_update_and_service(self, server):
        srv, _ = server
        base = "http://127.0.0.1:%d" % srv.port
        post(base + "/update", {"name": "wf1", "mode": "master",
                                "slaves": [{"id": "s1"}], "runtime": 12})
        with urllib.request.urlopen(base + "/service", timeout=5) as resp:
            data = json.loads(resp.read().decode())
        (key, status), = data.items()
        assert status["name"] == "wf1" and len(status["slaves"]) == 1

    def test_dashboard_html_and_plots(self, server):
        srv, tmp_path = server
        (tmp_path / "loss.png").write_bytes(b"\x89PNG fake")
        base = "http://127.0.0.1:%d" % srv.port
        with urllib.request.urlopen(base + "/", timeout=5) as resp:
            html = resp.read().decode()
        assert "loss.png" in html
        with urllib.request.urlopen(base + "/plots/loss.png",
                                    timeout=5) as resp:
            assert resp.read() == b"\x89PNG fake"
        # path traversal blocked
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/plots/../secret", timeout=5)

    def test_update_payloads_escaped_and_coerced(self, server):
        # regression: /update is unauthenticated — hostile payloads must
        # neither script-inject nor 500 the dashboard
        srv, _ = server
        base = "http://127.0.0.1:%d" % srv.port
        post(base + "/update", {"name": "<script>alert(1)</script>",
                                "mode": "<b>x</b>", "runtime": "12s",
                                "slaves": "not-a-list"})
        with urllib.request.urlopen(base + "/", timeout=5) as resp:
            html = resp.read().decode()
        assert "<script>alert(1)</script>" not in html
        assert "&lt;script&gt;" in html
        # unhashable/heterogeneous ids must not 500 /update or /
        post(base + "/update", {"id": [1, 2], "name": "l"})
        post(base + "/update", {"id": 5, "name": "n"})
        with urllib.request.urlopen(base + "/", timeout=5) as resp:
            assert resp.status == 200

    def test_live_workflow_graph(self, server):
        """The dashboard renders the running workflow's
        unit DAG (posted by the notifier) as an SVG with activity
        counters — the reference's viz.js graph page."""
        from veles_tpu.dummy import DummyLauncher
        from veles_tpu.models.mlp import MLPWorkflow

        rng = numpy.random.RandomState(0)
        wf = MLPWorkflow(
            DummyLauncher(), layers=(8, 10),
            loader_kwargs=dict(
                data=rng.rand(120, 16).astype(numpy.float32),
                labels=rng.randint(0, 10, 120).astype(numpy.int32),
                class_lengths=[0, 20, 100], minibatch_size=20),
            learning_rate=0.1, max_epochs=1, name="graph-wf")
        wf.initialize()
        wf.run()
        graph = wf.graph_snapshot()
        assert any(n["runs"] > 0 for n in graph["nodes"])
        assert graph["edges"]
        srv, _ = server
        base = "http://127.0.0.1:%d" % srv.port
        post(base + "/update", {"id": "g1", "name": "graph-wf",
                                "graph": graph})
        with urllib.request.urlopen(base + "/graph/g1.svg",
                                    timeout=5) as resp:
            svg = resp.read().decode()
        assert svg.startswith("<svg")
        assert "Repeater" in svg and "marker-end" in svg
        with urllib.request.urlopen(base + "/", timeout=5) as resp:
            html = resp.read().decode()
        assert "/graph/g1.svg" in html
        # malformed graph payloads must answer CLEANLY — a 404 or an
        # empty SVG, never a wedged connection / 500 (the /update
        # endpoint is unauthenticated)
        for bad in ("nope", {"nodes": 1}, {"nodes": [7], "edges": [3]}):
            post(base + "/update", {"id": "bad", "graph": bad})
            try:
                with urllib.request.urlopen(base + "/graph/bad.svg",
                                            timeout=5) as resp:
                    body = resp.read().decode()
                assert body.startswith("<svg") and "<rect" not in body
            except urllib.error.HTTPError as err:
                assert err.code == 404
        # keys that need percent-encoding round-trip through the page
        post(base + "/update", {"id": "my wf", "name": "my wf",
                                "graph": graph})
        with urllib.request.urlopen(base + "/graph/my%20wf.svg",
                                    timeout=5) as resp:
            assert resp.read().decode().startswith("<svg")

    def test_live_stream_pushes_plot_refresh(self, server):
        """/stream is an SSE feed —
        one state event on connect, another when a plot file lands or
        is re-rendered (mtime bump) — driving one full refresh cycle
        the way the dashboard JS does."""
        srv, tmp_path = server
        srv.STREAM_POLL = 0.05
        base = "http://127.0.0.1:%d" % srv.port
        post(base + "/update", {"name": "wf-live", "mode": "master",
                                "runtime": 1})

        def next_event(resp):
            payload = []
            while True:
                line = resp.readline().decode()
                if line.startswith("data:"):
                    payload.append(line[len("data:"):].strip())
                elif line.strip() == "" and payload:
                    return json.loads("".join(payload))

        resp = urllib.request.urlopen(base + "/stream", timeout=10)
        try:
            first = next_event(resp)
            assert first["workflows"][0]["name"] == "wf-live"
            assert first["plots"] == []
            # a plot renders -> the stream pushes the new state
            (tmp_path / "loss.png").write_bytes(b"\x89PNG live")
            second = next_event(resp)
            assert second["plots"][0]["name"] == "loss.png"
            stamp = second["plots"][0]["mtime"]
            # re-render (mtime bump) -> another push with a new
            # cache-buster
            os.utime(tmp_path / "loss.png", (stamp + 5, stamp + 5))
            third = next_event(resp)
            assert third["plots"][0]["mtime"] == stamp + 5
        finally:
            resp.close()
        # the polling fallback sees the same state
        with urllib.request.urlopen(base + "/plots.json",
                                    timeout=5) as r:
            plots = json.loads(r.read().decode())
        assert plots[0]["name"] == "loss.png"

    def test_notifier(self, server):
        srv, _ = server

        class FakeAgent:
            @staticmethod
            def fleet_status():
                return {"slaves": [{"id": "s1"}, {"id": "s2"}]}

        class FakeLauncher:
            workflow = type("W", (), {"name": "notified"})()
            mode = "master"
            agent = FakeAgent()

        notifier = StatusNotifier(
            FakeLauncher(), url="http://127.0.0.1:%d/update" % srv.port)
        assert notifier.notify_once()
        statuses = srv.statuses()
        status = next(iter(statuses.values()))
        assert status["name"] == "notified"
        assert len(status["slaves"]) == 2

    def test_live_plot_viewer_cache_busting(self, server):
        """The remote live-plot viewer (reference epgm multicast role):
        plot <img> tags carry an mtime cache-buster so the 3s
        meta-refresh re-fetches re-rendered figures, and the query
        string is stripped when serving."""
        srv, tmp_path = server
        (tmp_path / "err.png").write_bytes(b"\x89PNG v1")
        base = "http://127.0.0.1:%d" % srv.port
        with urllib.request.urlopen(base + "/", timeout=5) as resp:
            html = resp.read().decode()
        assert 'src="/plots/err.png?t=' in html
        # the busted URL must serve the CURRENT bytes
        import re
        url = re.search(r'src="(/plots/err\.png\?t=\d+)"', html).group(1)
        with urllib.request.urlopen(base + url, timeout=5) as resp:
            assert resp.read() == b"\x89PNG v1"


class TestContinuousDecoder:
    """Continuous batching: sequences joining mid-flight must decode
    exactly what single-request generate() produces."""

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

    def test_staggered_requests_match_generate(self, model):
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(1)
        prompts = [rng.randint(0, vocab, n) for n in (5, 3, 7, 4, 6)]
        budgets = [6, 4, 5, 7, 3]

        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=32, n_tokens=8)
        # two requests start; the rest join as slots free up
        ids = [dec.submit(prompts[0], budgets[0]),
               dec.submit(prompts[1], budgets[1])]
        dec.step()
        ids.append(dec.submit(prompts[2], budgets[2]))  # queued: full
        dec.step()
        dec.step()
        dec.step()  # request 1 (budget 4) retires here or earlier
        ids.append(dec.submit(prompts[3], budgets[3]))
        ids.append(dec.submit(prompts[4], budgets[4]))
        results = dec.run_until_drained()

        for rid, prompt, budget in zip(ids, prompts, budgets):
            want, _ = generate(params, table,
                               jnp.asarray(prompt)[None], heads,
                               n_tokens=budget)
            assert results[rid] == numpy.asarray(want)[0].tolist(), \
                "request %d diverged from single-request decode" % rid
        assert not dec.busy
        assert dec.tokens_out == sum(budgets)

    def test_eos_retires_early_and_slot_recycles(self, model):
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(2)
        prompt = rng.randint(0, vocab, 5)
        ref, _ = generate(params, table, jnp.asarray(prompt)[None],
                          heads, n_tokens=8)
        ref = numpy.asarray(ref)[0].tolist()
        eos = ref[2]
        # a sequence stops at its FIRST eos occurrence (greedy decode
        # often repeats tokens, so derive the expectation from ref)
        expect = ref[:ref.index(eos) + 1]
        dec = ContinuousDecoder(params, table, heads, slots=1,
                                max_len=32, n_tokens=8, eos=eos)
        first = dec.submit(prompt)
        second = dec.submit(prompt)  # queued until the slot recycles
        results = dec.run_until_drained()
        assert results[first] == expect
        assert results[second] == expect
        assert len(expect) < len(ref)  # it really did stop early

    def test_step_many_matches_stepwise(self, model):
        """The chunked throughput mode produces the same token streams
        as per-token stepping (tail tokens past a budget discarded)."""
        from veles_tpu.serving import ContinuousDecoder

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(3)
        prompts = [rng.randint(0, vocab, n) for n in (4, 6, 5)]
        budgets = [5, 9, 3]

        ref = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=32, n_tokens=8)
        ref_ids = [ref.submit(p, b) for p, b in zip(prompts, budgets)]
        ref.run_until_drained()

        fast = ContinuousDecoder(params, table, heads, slots=2,
                                 max_len=32, n_tokens=8)
        ids = [fast.submit(p, b) for p, b in zip(prompts, budgets)]
        fast.run_until_drained(chunk=4)

        for a, b in zip(ref_ids, ids):
            assert ref.results[a] == fast.results[b]
        assert fast.tokens_out == sum(budgets)

    def test_drain_pipelined_matches_stepwise(self, model):
        """The lag-1 pipelined drain (readback hidden behind the next
        chunk) yields the same streams as per-token stepping."""
        from veles_tpu.serving import ContinuousDecoder

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(4)
        prompts = [rng.randint(0, vocab, n) for n in (4, 6, 5, 3)]
        budgets = [5, 9, 3, 7]

        ref = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=48, n_tokens=9)
        ref_ids = [ref.submit(p, b) for p, b in zip(prompts, budgets)]
        ref.run_until_drained()

        piped = ContinuousDecoder(params, table, heads, slots=2,
                                  max_len=48, n_tokens=9)
        ids = [piped.submit(p, b) for p, b in zip(prompts, budgets)]
        piped.drain_pipelined(chunk=4)

        for a, b in zip(ref_ids, ids):
            assert ref.results[a] == piped.results[b]
        assert piped.tokens_out == sum(budgets)
        assert not piped.busy

    def test_sampled_streams_match_generate_per_request(self, model):
        """Temperature sampling: each request draws from its OWN key
        stream (fold_in(base, rid)), so its tokens equal
        generate(batch=1, key=that key) no matter which slot it lands
        in or who shares the batch — and two requests with the same
        prompt still differ."""
        import jax
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(5)
        prompts = [rng.randint(0, vocab, n) for n in (5, 5, 4)]
        base = jax.random.key(99)
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=32, n_tokens=6,
                                temperature=0.8, key=base)
        ids = [dec.submit(p) for p in prompts]
        results = dec.run_until_drained()
        for rid, prompt in zip(ids, prompts):
            want, _ = generate(params, table,
                               jnp.asarray(prompt)[None], heads,
                               n_tokens=6, temperature=0.8,
                               key=jax.random.fold_in(base, rid))
            assert results[rid] == numpy.asarray(want)[0].tolist(), \
                "request %d sampled stream diverged" % rid
        # same prompt, different request ids -> different streams
        assert results[ids[0]] != results[ids[1]]

    def test_bucketed_admission_across_prompt_lengths(self, model):
        """Prompts landing in different power-of-two buckets (the
        right-padded prefill path) decode the same tokens as
        generate(); the pad positions never leak into the stream."""
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(6)
        # bucket 16, bucket 32 and an exact-bucket length
        prompts = [rng.randint(0, vocab, n) for n in (7, 20, 16)]
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=64, n_tokens=5)
        ids = [dec.submit(p) for p in prompts]
        results = dec.run_until_drained()
        for rid, prompt in zip(ids, prompts):
            want, _ = generate(params, table,
                               jnp.asarray(prompt)[None], heads,
                               n_tokens=5, max_len=64)
            assert results[rid] == numpy.asarray(want)[0].tolist(), \
                "prompt len %d diverged through the padded prefill" \
                % len(prompt)

    def test_budget_overflow_rejected(self, model):
        from veles_tpu.serving import ContinuousDecoder

        params, table, heads, vocab = model
        dec = ContinuousDecoder(params, table, heads, slots=1,
                                max_len=16, n_tokens=8)
        with pytest.raises(ValueError):
            dec.submit(numpy.arange(12) % vocab)

    def test_batched_admission_one_dispatch_per_bucket(self, model):
        """The admission perf contract (docs/serving_performance.md):
        every same-bucket queued prompt admits in ONE slot_admit_many
        dispatch — the dispatch-counting CI hook proves it — and the
        streams stay bit-identical to single-request generate()."""
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(11)
        # three prompts in bucket 16, one in bucket 32
        prompts = [rng.randint(0, vocab, n) for n in (5, 9, 12, 20)]
        dec = ContinuousDecoder(params, table, heads, slots=4,
                                max_len=64, n_tokens=4)
        ids = [dec.submit(p) for p in prompts]
        dec.step()  # admits everything queued
        assert dec.dispatch_counts["admit"] == 2  # one per bucket group
        assert dec.dispatch_counts["admit_requests"] == 4
        results = dec.run_until_drained()
        for rid, prompt in zip(ids, prompts):
            want, _ = generate(params, table,
                               jnp.asarray(prompt)[None], heads,
                               n_tokens=4, max_len=64)
            assert results[rid] == numpy.asarray(want)[0].tolist()

    def test_tiled_pipelined_join_cancel_bit_identity(self, model):
        """The full PR-3 composite on the numerical contract: a small
        span tile (spans vary as sequences grow), batched admission,
        the lag-1 pipelined drain, requests joining mid-flight AND one
        cancelled mid-chunk — surviving streams exactly equal greedy
        generate()."""
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(12)
        prompts = [rng.randint(0, vocab, n) for n in (4, 6, 5, 3)]
        budgets = [5, 9, 3, 7]
        dec = ContinuousDecoder(params, table, heads, slots=2,
                                max_len=48, n_tokens=9, tile=8)
        # the victim is submitted FIRST so it owns a slot immediately:
        # cancelling it at pass 2 happens while the pass-1 chunk that
        # contains its tokens is still in flight (a true mid-chunk
        # cancel), and the freed slot re-admits a queued request
        victim = dec.submit(rng.randint(0, vocab, 5), 9)
        ids = [dec.submit(prompts[0], budgets[0]),
               dec.submit(prompts[1], budgets[1])]
        late = list(zip(prompts[2:], budgets[2:]))
        state = {"passes": 0}

        def admit():
            state["passes"] += 1
            if state["passes"] == 2:
                # cancel with a chunk in flight: its tail tokens must
                # be discarded at collect, the slot recycled cleanly
                assert dec.cancel(victim)
            if late:
                prompt, budget = late.pop(0)
                ids.append(dec.submit(prompt, budget))

        dec.drain_pipelined(chunk=4, admit=admit)
        assert victim not in dec.results
        assert not dec.busy
        for rid, prompt, budget in zip(ids, prompts, budgets):
            want, _ = generate(params, table,
                               jnp.asarray(prompt)[None], heads,
                               n_tokens=budget, max_len=48)
            assert dec.results[rid] == \
                numpy.asarray(want)[0].tolist(), \
                "request %d diverged under tile+pipeline+cancel" % rid

    def test_quantized_slot_streams_match_generate(self, model):
        """The int8 serving tiers plumbed into the slot engine: with
        quantize="int8" (W8A16 weights) and "int8-kv" (plus int8 slot
        KV cache) a request's stream equals generate() under the SAME
        quantize mode — asserted exactly on CPU."""
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import ContinuousDecoder
        import jax.numpy as jnp

        params, table, heads, vocab = model
        rng = numpy.random.RandomState(13)
        prompts = [rng.randint(0, vocab, n) for n in (5, 3, 7)]
        for mode in ("int8", "int8-kv"):
            dec = ContinuousDecoder(params, table, heads, slots=2,
                                    max_len=32, n_tokens=6,
                                    quantize=mode)
            ids = [dec.submit(p) for p in prompts]
            results = dec.run_until_drained()
            for rid, prompt in zip(ids, prompts):
                want, _ = generate(params, table,
                                   jnp.asarray(prompt)[None], heads,
                                   n_tokens=6, max_len=32,
                                   quantize=mode)
                assert results[rid] == \
                    numpy.asarray(want)[0].tolist(), \
                    "quantize=%s request %d diverged" % (mode, rid)

    def test_live_driver_lag1_pipelining_and_bit_identity(self, model):
        """The GenerateAPI driver is lag-1 double-buffered: the
        dispatch log shows chunk N+1 dispatched BEFORE chunk N is
        collected, streams stay bit-identical to generate(), a request
        joining mid-flight completes, and the health window records
        ttft/queue-wait percentiles."""
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import GenerateAPI
        import jax.numpy as jnp

        params, table, heads, vocab = model
        api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                          n_tokens=6, chunk=2, port=0)
        api.decoder.dispatch_log = log = []
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            rng = numpy.random.RandomState(14)
            prompts = [rng.randint(0, vocab, n).tolist()
                       for n in (4, 6, 5)]
            results = {}

            def call(i):
                results[i] = post(url, {"tokens": prompts[i]},
                                  timeout=60)

            threads = [threading.Thread(target=call, args=(0,)),
                       threading.Thread(target=call, args=(1,))]
            for t in threads:
                t.start()
            # the third request joins while the first two are decoding
            t_late = threading.Thread(target=call, args=(2,))
            t_late.start()
            for t in threads + [t_late]:
                t.join(timeout=90)
            for i, prompt in enumerate(prompts):
                want, _ = generate(params, table,
                                   jnp.asarray(prompt)[None], heads,
                                   n_tokens=6, max_len=32)
                assert results[i]["tokens"] == \
                    numpy.asarray(want)[0].tolist()
            # lag-1: somewhere in the trace two dispatches run
            # back-to-back with no intervening collect (the second
            # chunk is enqueued while the first is still uncollected)
            kinds = [entry[0] for entry in log
                     if entry[0] in ("dispatch", "collect")]
            assert any(a == b == "dispatch"
                       for a, b in zip(kinds, kinds[1:])), kinds
            # the latency windows saw the requests
            lat = api.health.snapshot()["latency_ms"]
            assert lat["ttft"]["count"] >= 3
            assert lat["queue_wait"]["count"] >= 3
            assert lat["ttft"]["p95"] is not None
        finally:
            api.stop()

    def test_generate_api_http_roundtrip(self, model):
        """The LLM serving HTTP surface: concurrent POSTs batch into
        the slot pool, each answer equals single-request generate()."""
        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import GenerateAPI
        import jax.numpy as jnp

        params, table, heads, vocab = model
        api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                          n_tokens=5, chunk=2, port=0)
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            rng = numpy.random.RandomState(7)
            prompts = [rng.randint(0, vocab, n).tolist()
                       for n in (4, 6, 5)]
            results = {}

            def call(i):
                results[i] = post(url, {"tokens": prompts[i]},
                                  timeout=60)

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=90)
            for i, prompt in enumerate(prompts):
                want, _ = generate(params, table,
                                   jnp.asarray(prompt)[None], heads,
                                   n_tokens=5, max_len=32)
                assert results[i]["tokens"] == \
                    numpy.asarray(want)[0].tolist()
            # malformed requests 400 cleanly
            for payload in ({"tokens": []}, {"tokens": "x"},
                            {"tokens": [vocab + 5]},
                            {"tokens": [1], "n_tokens": 0},
                            {"tokens": list(range(3)) * 20}):
                with pytest.raises(urllib.error.HTTPError) as err:
                    post(url, payload)
                assert err.value.code == 400
        finally:
            api.stop()

    def test_generate_api_driver_failure_sheds_then_heals(self, model):
        """A device/runtime error in the driver loop must resolve every
        in-flight request with a retryable error (no 300 s timeout
        wedge), trip the breaker, and SELF-HEAL: the decoder is rebuilt
        from the held params and a retried request succeeds without a
        process restart (docs/serving_robustness.md)."""
        import time

        from veles_tpu.parallel.decode import generate
        from veles_tpu.serving import GenerateAPI
        import jax.numpy as jnp

        params, table, heads, vocab = model
        api = GenerateAPI(params, table, heads, slots=1, max_len=32,
                          n_tokens=4, chunk=2, port=0,
                          rebuild_backoff=0.02)
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port

            def boom(*a, **k):
                raise RuntimeError("injected device failure")

            api.decoder.dispatch_chunk = boom
            with pytest.raises(urllib.error.HTTPError) as err:
                post(url, {"tokens": [1, 2, 3]}, timeout=30)
            assert err.value.code == 503  # shed, retryable
            assert "injected device failure" in \
                err.value.read().decode()
            # the breaker tripped and the rebuild closes it again
            deadline = time.time() + 30
            while not api.health.ready and time.time() < deadline:
                time.sleep(0.02)
            assert api.health.ready, api.health.snapshot()
            snap = api.health.snapshot()
            assert snap["counters"]["trips"] == 1
            assert snap["counters"]["rebuilds"] == 1
            assert snap["counters"]["shed"] == 1
            # the rebuilt decoder serves correct tokens (the injected
            # failure died with the old decoder instance)
            out = post(url, {"tokens": [2, 3]}, timeout=60)
            want, _ = generate(params, table,
                               jnp.asarray([2, 3])[None], heads,
                               n_tokens=4, max_len=32)
            assert out["tokens"] == numpy.asarray(want)[0].tolist()
        finally:
            api.stop()
