"""Zero-downtime deploys: live hot-swap, blue-green rollout, and the
persistent executable cache (veles_tpu/serving.py, veles_tpu/rollout.py,
veles_tpu/aot/exec_cache.py; docs/zero_downtime.md).

Fast tier covers the swap seam (outputs change, rollback restores
bit-identically, poisoned checkpoints are refused with the old weights
still serving, zero 5xx across the swap window), the rollback
predicate's edge cases driven as a unit with explicit clocks (zero
green traffic, blue-baseline suppression, dwell hysteresis), and the
torn-cache discipline (truncated or tampered entries refuse loudly
once, unlink, and fall back to live compilation).

The ``slow``-marked chaos tier boots real engines: a seeded bad-green
ramp must auto-roll back naming the leading indicator in the incident
artifact with zero shed requests and blue streams bit-identical, a
clean green must promote, and the poisoned-swap profile must be
refused end to end.
"""

import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request
import zlib

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.parallel.transformer_step import init_transformer_params
from veles_tpu.rollout import (BURN_SERIES, SWAP_SERIES, TTFT_SERIES,
                               BlueGreenRollout, RolloutConfig)

pytestmark = pytest.mark.deploy

HEADS, EMBED, VOCAB = 4, 16, 11


def _model():
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, 2, EMBED, HEADS, VOCAB)
    table = jnp.asarray(rng.randn(VOCAB, EMBED).astype(numpy.float32) * 0.3)
    params2 = init_transformer_params(numpy.random.RandomState(99),
                                      2, EMBED, HEADS, VOCAB)
    return params, table, params2


def _post(url, payload, timeout=60, tenant=None):
    headers = {"Content-Type": "application/json"}
    if tenant:
        headers["X-Veles-Tenant"] = tenant
    req = urllib.request.Request(url, data=json.dumps(payload).encode(),
                                 headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def _pt(tenant):
    return (zlib.crc32(tenant.encode()) % 10000) / 10000.0


def _tenants():
    """A tenant hashing inside the 10%% green slice and one safely
    blue at that fraction."""
    green = next("t%d" % i for i in range(1000) if _pt("t%d" % i) < 0.1)
    blue = next("t%d" % i for i in range(1000) if _pt("t%d" % i) > 0.5)
    return green, blue


def _api(params, table, chaos=None):
    from veles_tpu.serving import GenerateAPI
    return GenerateAPI(params, table, HEADS, slots=2, max_len=32,
                       n_tokens=5, chunk=2, port=0, chaos=chaos)


def _poison(params):
    leaves, tree = jax.tree.flatten(params)
    leaves[0] = jnp.full_like(leaves[0], float("nan"))
    return jax.tree.unflatten(tree, leaves)


# -- live weight hot-swap ----------------------------------------------------

class TestHotSwap:

    def test_swap_rollback_and_poison_refusal(self):
        """The full seam in one boot: a swap changes outputs, rollback
        restores the old weights bit-identically, and a NaN-poisoned
        checkpoint is refused with the old weights still serving."""
        params, table, params2 = _model()
        api = _api(params, table)
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            r1 = _post(url, {"tokens": [1, 2, 3]})
            assert api.swap_params(params2, version="v2") is True
            assert api.version == "v2"
            assert api.health.counter("param_swaps") == 1
            hz = json.loads(urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % api.port,
                timeout=30).read().decode())
            assert hz["version"] == "v2"
            assert "rollout" not in hz
            r2 = _post(url, {"tokens": [1, 2, 3]})
            assert r1["tokens"] != r2["tokens"], "swap must change outputs"

            api.rollback_swap()
            r3 = _post(url, {"tokens": [1, 2, 3]})
            assert r3["tokens"] == r1["tokens"], \
                "rollback must restore the old weights bit-identically"

            with pytest.raises(RuntimeError, match="non-finite"):
                api.swap_params(_poison(params2), version="poison")
            r4 = _post(url, {"tokens": [1, 2, 3]})
            assert r4["tokens"] == r1["tokens"], \
                "old weights must keep serving after a refused swap"
            assert api.health.counter("swap_failures") == 1
        finally:
            api.stop()

    def test_zero_5xx_across_swap_window(self):
        """A client hammering /generate through the drain-then-swap
        window sees only 200s — the seam holds requests, it never
        sheds them."""
        params, table, params2 = _model()
        api = _api(params, table)
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            _post(url, {"tokens": [1, 2]})  # warm the decode programs
            codes, errors, stop = [], [], threading.Event()

            def pound():
                while not stop.is_set():
                    try:
                        _post(url, {"tokens": [2, 3]}, timeout=30)
                        codes.append(200)
                    except urllib.error.HTTPError as exc:
                        codes.append(exc.code)
                    except Exception as exc:  # noqa: BLE001
                        errors.append(exc)

            thread = threading.Thread(target=pound)
            thread.start()
            try:
                time.sleep(0.2)
                assert api.swap_params(params2, version="v2") is True
            finally:
                stop.set()
                thread.join(60)
            assert not errors, errors
            assert codes and all(code == 200 for code in codes), \
                "shed requests across the swap window: %r" % (
                    [c for c in codes if c != 200],)
        finally:
            api.stop()


# -- rollback predicate edge cases (unit, explicit clock) --------------------

class _RecordingGovernor:
    def __init__(self):
        self.notes = []

    def note_deploy(self, action, api, reason="", **attrs):
        self.notes.append((action, reason, attrs))

    def actions(self):
        return [action for action, _, _ in self.notes]


class _FakeApi:
    slo = None

    def __init__(self):
        self.governor = _RecordingGovernor()


@pytest.fixture()
def no_history():
    """Detach the process metric history so predicate units neither
    read nor write ambient detector state."""
    from veles_tpu.observe.history import (get_metric_history,
                                           set_metric_history)
    previous = get_metric_history()
    set_metric_history(None)
    try:
        yield
    finally:
        set_metric_history(previous)


class TestRollbackPredicate:

    def test_zero_green_traffic_yields_no_verdict(self, no_history):
        """An idle green slice neither rolls back nor advances the
        ladder — and it resets the breach streak."""
        cfg = RolloutConfig(steps=(0.1, 1.0), hold_s=100.0,
                            cooldown_s=100.0, window_s=60.0,
                            min_requests=4, interval_s=0.01)
        rollout = BlueGreenRollout("v2", config=cfg)
        api = _FakeApi()
        rollout._breaches = 1  # stale breach from a busier rung
        for _ in range(10):
            rollout.note_resolved("blue", True, now=99.0)
        rollout.note_resolved("green", False, now=99.0)  # < min_requests
        rollout.tick(api, now=100.0)
        assert rollout.state == "shifting"
        assert rollout.step_index == 0
        assert rollout._breaches == 0
        assert "deploy_rollback" not in api.governor.actions()

    def test_blue_baseline_burning_suppresses_rollback(self, no_history):
        """When blue burns past the veto the regression is ambient:
        no rollback, a cooldown-limited suppression note instead."""
        cfg = RolloutConfig(steps=(0.1, 1.0), hold_s=100.0,
                            cooldown_s=1.0, window_s=60.0,
                            min_requests=2, burn_ratio=2.0,
                            burn_floor=0.01, blue_burn_veto=5.0,
                            breach_for=1, interval_s=0.01)
        rollout = BlueGreenRollout("v2", config=cfg)
        api = _FakeApi()
        for _ in range(10):
            rollout.note_resolved("green", False, now=100.0)
        for i in range(10):
            rollout.note_resolved("blue", i % 2 == 0, now=100.0)
        # green burn 100x, blue burn 50x: green IS worse by ratio, but
        # blue's own burn is far past the veto
        rollout.tick(api, now=100.5)
        assert rollout.state == "shifting"
        assert rollout.suppressed_total == 1
        actions = api.governor.actions()
        assert "deploy_rollback" not in actions
        assert actions.count("deploy_rollback_suppressed") == 1
        _, reason, attrs = next(
            note for note in api.governor.notes
            if note[0] == "deploy_rollback_suppressed")
        assert "blue baseline burning" in reason
        assert attrs["blue_burn"] >= cfg.blue_burn_veto
        # within the cooldown: suppression counts, but no second note
        rollout.tick(api, now=100.6)
        assert rollout.suppressed_total == 2
        assert api.governor.actions().count(
            "deploy_rollback_suppressed") == 1
        # past the cooldown the note fires again
        rollout.tick(api, now=102.0)
        assert api.governor.actions().count(
            "deploy_rollback_suppressed") == 2

    def test_breach_streak_hysteresis(self, no_history):
        """One bad window does not roll back when breach_for=2; a
        second consecutive one does, naming the plane."""
        cfg = RolloutConfig(steps=(0.1, 1.0), hold_s=100.0,
                            cooldown_s=0.1, window_s=60.0,
                            min_requests=2, burn_ratio=2.0,
                            burn_floor=0.01, blue_burn_veto=1000.0,
                            breach_for=2, interval_s=0.01)
        rollout = BlueGreenRollout("v2", config=cfg)
        api = _FakeApi()
        for _ in range(10):
            rollout.note_resolved("green", False, now=100.0)
            rollout.note_resolved("blue", True, now=100.0)
        rollout.tick(api, now=100.5)
        assert rollout.state == "shifting"
        assert rollout._breaches == 1
        rollout.tick(api, now=100.6)
        assert rollout.state == "rolling_back"
        assert "burn" in rollout.reason
        assert "deploy_rollback" in api.governor.actions()

    def test_dwell_hysteresis_prevents_oscillation(self, no_history):
        """Clean ticks advance the ladder at most once per
        max(hold_s, cooldown_s) dwell — rapid ticking cannot sprint
        to full traffic."""
        cfg = RolloutConfig(steps=(0.1, 0.5, 1.0), hold_s=10.0,
                            cooldown_s=10.0, window_s=60.0,
                            min_requests=2, interval_s=0.01)
        rollout = BlueGreenRollout("v2", config=cfg)
        api = _FakeApi()

        def feed(now):
            for _ in range(6):
                rollout.note_resolved("green", True, now=now)
                rollout.note_resolved("blue", True, now=now)

        feed(100.0)
        rollout.tick(api, now=100.0)  # anchors started_at/_last_shift
        for now in (101.0, 104.0, 109.0):
            rollout.tick(api, now=now)
        assert rollout.step_index == 0, "shifted before the dwell"
        feed(110.0)
        rollout.tick(api, now=110.5)
        assert rollout.step_index == 1
        rollout.tick(api, now=111.0)  # immediately after a shift
        assert rollout.step_index == 1, "oscillated inside the dwell"
        feed(121.0)
        rollout.tick(api, now=121.0)
        assert rollout.step_index == 2

    def test_routing_is_fixed_point_and_monotonic(self):
        """Raising the fraction only ADDS tenants to green; rollback
        sends everyone back to blue."""
        cfg = RolloutConfig(steps=(0.1, 0.5, 1.0))
        rollout = BlueGreenRollout("v2", config=cfg)
        tenants = ["t%d" % i for i in range(64)]
        greens = []
        for step in range(len(cfg.steps)):
            rollout.step_index = step
            greens.append({t for t in tenants if rollout.routes_green(t)})
        assert greens[0] <= greens[1] <= greens[2]
        assert greens[2] == set(tenants)
        rollout.state = "rolled_back"
        assert not any(rollout.routes_green(t) for t in tenants)


# -- persistent executable cache: torn-write discipline ----------------------

class TestExecCacheTornEntry:

    def _cache(self, tmp_path):
        from veles_tpu.aot.exec_cache import ExecutableCache
        return ExecutableCache(str(tmp_path / "xcache"))

    def _compiled(self):
        fn = jax.jit(lambda x: x * 2.0 + 1.0)
        return fn.lower(jnp.arange(4.0)).compile()

    def test_round_trip(self, tmp_path):
        """Stored on one device, loaded in a process with eight: the
        executable comes back on ITS device, not on all of them."""
        assert len(jax.local_devices()) > 1
        cache = self._cache(tmp_path)
        assert cache.load("k") is None and cache.misses == 1
        assert cache.store("k", self._compiled()) is True
        loaded = cache.load("k")
        assert loaded is not None and cache.hits == 1
        expect = numpy.asarray(jnp.arange(4.0) * 2.0 + 1.0)
        numpy.testing.assert_allclose(
            numpy.asarray(loaded(jnp.arange(4.0))), expect)

    def test_entry_for_a_device_this_process_lacks_is_a_miss(
            self, tmp_path, monkeypatch):
        cache = self._cache(tmp_path)
        cache.store("k", self._compiled())
        monkeypatch.setattr(jax, "local_devices", lambda: [])
        assert cache.load("k") is None
        assert (cache.misses, cache.rejects, cache.hits) == (1, 0, 0)
        # a miss, not a reject: the entry stays for a process that fits
        assert os.path.isfile(cache._path("k"))

    def test_torn_entry_refused_loudly_once_and_unlinked(
            self, tmp_path, caplog):
        """A truncated entry (sidecar intact) is rejected with ONE
        warning, unlinked so the next compile repairs it, and counted
        as a reject+miss — never executed."""
        from veles_tpu.serving_chaos import tear_file
        cache = self._cache(tmp_path)
        cache.store("k", self._compiled())
        path = cache._path("k")

        def _reject_records():
            return [r for r in caplog.records
                    if "refused" in r.getMessage()
                    and path in r.getMessage()]

        with caplog.at_level(logging.WARNING, logger="aot.ExecCache"):
            tear_file(path, frac=0.5)
            assert cache.load("k") is None
            assert cache.rejects == 1 and cache.misses == 1
            assert not (tmp_path / "xcache" / ("k" +
                        path.rsplit("k", 1)[-1])).exists()
            assert len(_reject_records()) == 1
            # the repaired-then-torn-again entry still refuses, but the
            # warning for this path already fired: warn-once holds
            cache.store("k", self._compiled())
            tear_file(path, frac=0.3)
            assert cache.load("k") is None
            assert cache.rejects == 2
            assert len(_reject_records()) == 1

    def test_tampered_entry_refused(self, tmp_path):
        """A bit-flip without a sidecar update fails the sha256 check."""
        cache = self._cache(tmp_path)
        cache.store("k", self._compiled())
        path = cache._path("k")
        with open(path, "rb+") as fobj:
            fobj.seek(-1, 2)
            last = fobj.read(1)
            fobj.seek(-1, 2)
            fobj.write(bytes([last[0] ^ 0xFF]))
        assert cache.load("k") is None
        assert cache.rejects == 1

    def test_missing_sidecar_refused(self, tmp_path):
        cache = self._cache(tmp_path)
        cache.store("k", self._compiled())
        os.remove(cache._path("k") + ".sha256")
        assert cache.load("k") is None
        assert cache.rejects == 1


# -- bench/regress contract --------------------------------------------------

class TestRegressDirections:

    def test_deploy_keys_are_lower_better(self):
        from veles_tpu.observe.regress import _lower_is_better
        assert _lower_is_better("coldstart_cached_to_first_token_ms")
        assert _lower_is_better("deploy_swap_shed_requests")
        assert _lower_is_better("deploy_swap_ms")

    def test_elastic_keys_directions(self):
        """The elastic bench keys (docs/elastic_serving.md): failover
        latency regresses UP; throughput, scale efficiency and the
        affinity hit rate regress DOWN (the higher-better default)."""
        from veles_tpu.observe.regress import _lower_is_better
        assert _lower_is_better("elastic_failover_ms")
        assert not _lower_is_better("elastic_tokens_per_sec_1replica")
        assert not _lower_is_better("elastic_tokens_per_sec_2replica")
        assert not _lower_is_better("elastic_scale_x")
        assert not _lower_is_better("elastic_affinity_hit_rate")


# -- the swap seam's reshard receipt (satellite: wire reshard into swap) -----

class TestSwapReshardSeam:

    def test_mesh_swap_is_slice_only_zero_wire_bytes(self):
        """The train->serve transition INSIDE the hot-swap seam: a
        host (train-layout) checkpoint swapped onto a live serve mesh
        must move 0 bytes on the wire — replicated -> sharded lowers
        to local slices, never a collective — and the swapped engine
        must stream bit-identically to a cold single-chip boot on the
        same checkpoint."""
        from veles_tpu.parallel.mesh import build_mesh
        from veles_tpu.serving import ContinuousDecoder
        # a mesh-divisible vocab (the tensor-parallel axis shards
        # heads/ffn/vocab; the module default VOCAB=11 cannot)
        vocab = 16
        rng = numpy.random.RandomState(0)
        params = init_transformer_params(rng, 2, EMBED, HEADS, vocab)
        table = jnp.asarray(
            rng.randn(vocab, EMBED).astype(numpy.float32) * 0.3)
        params2 = init_transformer_params(numpy.random.RandomState(99),
                                          2, EMBED, HEADS, vocab)
        mesh = build_mesh(devices=jax.devices()[:4], data=1, model=4)
        dec = ContinuousDecoder(params, table, HEADS, slots=2,
                                max_len=32, n_tokens=5, mesh=mesh)
        assert dec.last_swap_stats is None
        dec.swap_params(params2)
        stats = dec.last_swap_stats
        assert stats is not None, \
            "a mesh swap must leave its reshard receipt"
        assert stats["bytes"] == 0, \
            "host checkpoint -> serve mesh must be slice-only " \
            "(0 wire bytes), got %r" % (stats,)
        assert set(stats["counts"]) <= {"slice", "keep"}, \
            stats["counts"]
        # bit-identity across the seam: the hot-swapped mesh engine
        # equals a cold single-chip engine on the same checkpoint
        single = ContinuousDecoder(params2, table, HEADS, slots=2,
                                   max_len=32, n_tokens=5)
        prompts = [[1, 2, 3], [4, 5, 6, 7], [2, 2]]
        for p in prompts:
            dec.submit(p)
            single.submit(p)
        dec.run_until_drained(chunk=2)
        single.run_until_drained(chunk=2)
        assert dec.results == single.results

    def test_single_chip_swap_leaves_no_receipt(self):
        params, table, params2 = _model()
        from veles_tpu.serving import ContinuousDecoder
        dec = ContinuousDecoder(params, table, HEADS, slots=2,
                                max_len=32, n_tokens=5)
        dec.swap_params(params2)
        assert dec.last_swap_stats is None


# -- the deploy rollout CLI verb (satellite: fetch+verify+begin_rollout) -----

class _RolloutRecorder:
    """The injectable ``api`` seam: a live-enough GenerateAPI stand-in
    whose decoder carries the real tree structure."""

    class _Decoder:
        def __init__(self, params, table):
            self.params = params
            self.embed_table = table

    def __init__(self, params, table, refuse=None):
        self.decoder = self._Decoder(params, table)
        self.calls = []
        self._refuse = refuse

    def begin_rollout(self, new_params, new_embed_table=None,
                      version="green", timeout=120.0):
        if self._refuse is not None:
            raise self._refuse
        self.calls.append({"version": version, "timeout": timeout,
                           "params": new_params,
                           "table": new_embed_table})


class TestDeployRolloutCLI:

    def _package(self, tmp_path, params, table, tamper=False,
                 weights=True):
        """A real packed package: manifest + sha-sidecar'd serving
        checkpoint (forge/package.py conventions)."""
        import hashlib
        import veles_tpu.forge.package as pkg
        from veles_tpu.deploy_cli import save_serving_checkpoint
        d = tmp_path / ("pkg_tampered" if tamper else "pkg")
        d.mkdir()
        (d / "wf.py").write_text("# serving checkpoint carrier\n")
        artifacts = []
        if weights:
            with open(d / "weights.npz", "wb") as fout:
                save_serving_checkpoint(fout, params, table)
            digest = hashlib.sha256(
                (d / "weights.npz").read_bytes()).hexdigest()
            if tamper:
                digest = "0" * 64
            (d / "weights.npz.sha256").write_text(
                "%s  weights.npz\n" % digest)
            artifacts = ["weights.npz"]
        (d / "manifest.json").write_text(json.dumps({
            "name": "toy-serve", "version": "2.0", "workflow": "wf.py",
            "artifacts": artifacts}))
        path, _ = pkg.pack(str(d))
        return path

    def test_exit_code_matrix(self, tmp_path, monkeypatch):
        import veles_tpu.serving as serving
        from veles_tpu.deploy_cli import (EXIT_OK, EXIT_PACKAGE,
                                          EXIT_ROLLOUT, EXIT_TAMPERED,
                                          main, rollout_package)
        import io as _io
        params, table, params2 = _model()
        path = self._package(tmp_path, params2, table)
        sink = _io.StringIO()

        # 0: resolve + verify + begin_rollout, stamped name@version
        api = _RolloutRecorder(params, table)
        assert rollout_package(path, api=api, out=sink) == EXIT_OK
        assert len(api.calls) == 1
        assert api.calls[0]["version"] == "toy-serve@2.0"
        got = jax.tree.leaves((api.calls[0]["params"],
                               api.calls[0]["table"]))
        want = jax.tree.leaves((params2, table))
        for a, b in zip(got, want):
            numpy.testing.assert_array_equal(numpy.asarray(a),
                                             numpy.asarray(b))

        # 2: unresolvable / malformed / missing-weights packages
        assert rollout_package(str(tmp_path / "absent.tar.gz"),
                               api=api, out=sink) == EXIT_PACKAGE
        garbage = tmp_path / "garbage.tar.gz"
        garbage.write_bytes(b"not a tarball")
        assert rollout_package(str(garbage), api=api,
                               out=sink) == EXIT_PACKAGE
        nw_dir = tmp_path / "nw"
        nw_dir.mkdir()
        no_weights = self._package(nw_dir, params2, table,
                                   weights=False)
        assert rollout_package(no_weights, api=api,
                               out=sink) == EXIT_PACKAGE

        # 2: checkpoint that cannot assemble against the live tree
        mismatched = _RolloutRecorder({"only": table}, table)
        assert rollout_package(path, api=mismatched,
                               out=sink) == EXIT_PACKAGE
        assert mismatched.calls == []

        # 3: tampered artifact refused before any weight byte parses
        bad = self._package(tmp_path, params2, table, tamper=True)
        assert rollout_package(bad, api=api, out=sink) == EXIT_TAMPERED

        # 4: no live serving api in this process
        monkeypatch.setattr(serving, "_CURRENT_API", None)
        assert rollout_package(path, api=None, out=sink) == EXIT_ROLLOUT

        # 4: the live api refuses the rollout (one already in flight)
        busy = _RolloutRecorder(
            params, table, refuse=RuntimeError("already in flight"))
        assert rollout_package(path, api=busy, out=sink) == EXIT_ROLLOUT

        # the CLI surface maps straight through
        assert main(["rollout", path, "--timeout", "5"],
                    api=_RolloutRecorder(params, table)) == EXIT_OK

    def test_checkpoint_roundtrip(self, tmp_path):
        import io as _io
        from veles_tpu.deploy_cli import (load_serving_checkpoint,
                                          save_serving_checkpoint)
        params, table, _ = _model()
        buf = _io.BytesIO()
        save_serving_checkpoint(buf, params, table)
        got_params, got_table = load_serving_checkpoint(
            buf.getvalue(), params, table)
        for a, b in zip(jax.tree.leaves((params, table)),
                        jax.tree.leaves((got_params, got_table))):
            numpy.testing.assert_array_equal(numpy.asarray(a),
                                             numpy.asarray(b))
        with pytest.raises(ValueError, match="leaves"):
            load_serving_checkpoint(buf.getvalue(), {"one": table},
                                    table)


# -- chaos deploy proof (slow tier) ------------------------------------------

@pytest.fixture()
def isolated_history(tmp_path, monkeypatch):
    """A private MetricHistory + incident recorder so the deploy
    detector rules and artifacts are observable without ambient serve
    rules claiming the leading indicator."""
    import veles_tpu.observe.servescope as servescope
    from veles_tpu.observe.history import (IncidentRecorder,
                                           MetricHistory,
                                           get_metric_history,
                                           set_metric_history)
    from veles_tpu.observe.metrics import MetricsRegistry
    monkeypatch.setattr(servescope, "MIN_EVAL_TOKENS", 10 ** 9)
    history = MetricHistory(
        registry=MetricsRegistry(enabled=True), interval_s=0.01,
        capacity=256, series_cap=64, rules=[],
        incidents=IncidentRecorder(cooldown_s=0.0,
                                   directory=str(tmp_path)))
    previous = get_metric_history()
    set_metric_history(history)
    try:
        yield history
    finally:
        set_metric_history(previous)


@pytest.mark.slow
class TestDeployChaos:

    def test_clean_green_promotes_with_blue_bit_identical(self):
        params, table, params2 = _model()
        green_t, blue_t = _tenants()
        api = _api(params, table)
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            base_blue = _post(url, {"tokens": [1, 2, 3]}, tenant=blue_t)
            base_green = _post(url, {"tokens": [1, 2, 3]}, tenant=green_t)
            cfg = RolloutConfig(steps=(0.1, 1.0), hold_s=0.3,
                                cooldown_s=0.3, window_s=5.0,
                                min_requests=2, interval_s=0.05)
            rollout = api.begin_rollout(params2, version="v2", config=cfg)
            hz = json.loads(urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % api.port,
                timeout=30).read().decode())
            assert hz["rollout"]["version"] == "v2"
            assert hz["rollout"]["state"] == "shifting"
            g1 = _post(url, {"tokens": [1, 2, 3]}, tenant=green_t)
            b1 = _post(url, {"tokens": [1, 2, 3]}, tenant=blue_t)
            assert b1["tokens"] == base_blue["tokens"], \
                "blue streams must stay bit-identical during the ramp"
            assert g1["tokens"] != base_green["tokens"], \
                "green tenant should be on the new weights"
            deadline = time.time() + 120
            while rollout.state not in ("promoted", "rolled_back") \
                    and time.time() < deadline:
                _post(url, {"tokens": [2, 3]}, tenant=green_t)
                _post(url, {"tokens": [2, 3]}, tenant=blue_t)
                time.sleep(0.05)
            assert rollout.state == "promoted", rollout.snapshot()
            assert api.version == "v2"
            assert api.health.counter("promotes") == 1
            after = _post(url, {"tokens": [1, 2, 3]}, tenant=blue_t)
            assert after["tokens"] == g1["tokens"], \
                "after promote everyone serves v2"
        finally:
            api.stop()

    def test_bad_green_auto_rolls_back_naming_leading_indicator(
            self, isolated_history):
        """The seeded green-ramp chaos profile must trip the TTFT
        plane: auto-rollback with zero shed, blue bit-identical, and
        an incident artifact whose leading indicator names the green
        TTFT series."""
        from veles_tpu.serving_chaos import (ServingChaosConfig,
                                             ServingChaosMonkey)
        history = isolated_history
        params, table, params2 = _model()
        green_t, blue_t = _tenants()
        chaos = ServingChaosMonkey(ServingChaosConfig(
            deploy_green_ramp_ms=80.0, deploy_green_ramp_steps=3))
        api = _api(params, table, chaos=chaos)
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            base_blue = _post(url, {"tokens": [1, 2, 3]}, tenant=blue_t)
            cfg = RolloutConfig(steps=(0.1, 1.0), hold_s=30.0,
                                cooldown_s=0.5, window_s=10.0,
                                min_requests=2, interval_s=0.05,
                                ttft_ratio=1.5, ttft_floor_s=0.01,
                                breach_for=2)
            rollout = api.begin_rollout(params2, version="v2", config=cfg)
            deadline = time.time() + 120
            shed = 0
            while rollout.state not in ("promoted", "rolled_back") \
                    and time.time() < deadline:
                for tenant in (green_t, blue_t):
                    try:
                        _post(url, {"tokens": [2, 3]}, tenant=tenant)
                    except urllib.error.HTTPError:
                        shed += 1
            assert rollout.state == "rolled_back", rollout.snapshot()
            assert "ttft" in (rollout.reason or ""), rollout.reason
            assert shed == 0, "zero-shed contract violated: %d" % shed
            assert api.health.counter("rollbacks") == 1
            assert chaos.counters.get("green_ramp_stalls", 0) > 0
            after = _post(url, {"tokens": [1, 2, 3]}, tenant=blue_t)
            assert after["tokens"] == base_blue["tokens"], \
                "blue streams must stay bit-identical across the rollback"
            doc = history.incidents.last_doc
            assert doc is not None, "rollback must cut an incident artifact"
            leading = doc["leading_indicator"]
            assert leading["series"] == TTFT_SERIES, leading
            assert history.incidents.last_path is not None
        finally:
            api.stop()

    def test_poisoned_swap_profile_refused_with_artifact(
            self, isolated_history):
        from veles_tpu.serving_chaos import (ServingChaosConfig,
                                             ServingChaosMonkey)
        history = isolated_history
        params, table, params2 = _model()
        chaos = ServingChaosMonkey(ServingChaosConfig(
            deploy_poison_nan=True))
        api = _api(params, table, chaos=chaos)
        api.start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            r1 = _post(url, {"tokens": [1, 2, 3]})
            with pytest.raises(RuntimeError, match="non-finite"):
                api.swap_params(params2, version="v2")
            assert chaos.counters.get("poisoned_swaps") == 1
            assert api.health.counter("swap_failures") == 1
            r2 = _post(url, {"tokens": [1, 2, 3]})
            assert r2["tokens"] == r1["tokens"], \
                "old weights must keep serving after the refusal"
            doc = history.incidents.last_doc
            assert doc is not None
            assert doc["leading_indicator"]["series"] == SWAP_SERIES
        finally:
            api.stop()
