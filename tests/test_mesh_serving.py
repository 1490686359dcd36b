"""Mesh-sharded slot-engine serving (docs/sharded_serving.md): the
tensor-parallel layout path must stream bit-identical tokens to the
single-chip engine on the suite's 8-device virtual CPU mesh, keep the
KV slab sharded across dispatches, compile one program per
(bucket, group, layout) with zero recompile storms, and compose with
the measured train→serve reshard. `make mesh` runs this file +
test_reshard.py, mirroring `make chaos`."""

import numpy
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from veles_tpu.observe.metrics import MetricsRegistry
from veles_tpu.observe.xla_stats import get_compile_tracker
from veles_tpu.parallel.mesh import build_mesh
from veles_tpu.parallel.transformer_step import init_transformer_params
from veles_tpu.serving import ContinuousDecoder, build_serve_mesh

pytestmark = pytest.mark.mesh

HEADS, EMBED, BLOCKS, VOCAB = 8, 32, 2, 16


@pytest.fixture(scope="module")
def model():
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, BLOCKS, EMBED, HEADS, VOCAB)
    table = jnp.asarray(
        rng.randn(VOCAB, EMBED).astype(numpy.float32) * 0.3)
    return params, table


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(devices=jax.devices()[:8], data=1, model=8)


def _drain_pair(params, table, mesh, quantize=None, chunk=4):
    """One single-chip and one sharded decoder through the SAME
    composite drive: staggered submissions joining mid-flight, tiled
    spans, pipelined chunked drain. Returns (ref, got)."""
    rng = numpy.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, n)
               for n in (5, 9, 3, 7, 6, 11, 4)]
    out = []
    for m in (None, mesh):
        dec = ContinuousDecoder(params, table, HEADS, slots=3,
                                max_len=256, n_tokens=6,
                                quantize=quantize, tile=8, mesh=m)
        pending = list(prompts)
        for _ in range(3):
            dec.submit(pending.pop(0))
        dec.drain_pipelined(
            chunk,
            admit=lambda dec=dec, pending=pending:
                pending and dec.submit(pending.pop(0)))
        out.append(dec)
    return out


class TestShardedSlotEngine:
    @pytest.mark.parametrize("quantize", [None, "int8-kv"])
    def test_streams_bit_identical_to_single_chip(self, model, mesh,
                                                  quantize):
        """The acceptance composite: mid-flight joins, span tiling and
        the pipelined drain — sharded and single-chip engines must
        produce identical token streams for every request, for the
        bf16/f32 tier AND the int8-KV tier."""
        params, table = model
        ref, got = _drain_pair(params, table, mesh, quantize=quantize)
        assert ref.results.keys() == got.results.keys()
        for rid in ref.results:
            assert ref.results[rid] == got.results[rid], \
                "request %d diverged under the mesh" % rid

    def test_state_stays_sharded_across_dispatches(self, model, mesh):
        """The layout must survive admit/step/chunk round trips — a
        silently replicated KV slab would pass the token test while
        storing H x the memory per device."""
        params, table = model
        from jax.sharding import NamedSharding

        from veles_tpu.parallel.decode import slot_state_specs

        _, got = _drain_pair(params, table, mesh)
        assert not got.params["blocks"][0]["wqkv"] \
            .sharding.is_fully_replicated
        _, got8 = _drain_pair(params, table, mesh, quantize="int8-kv")
        for dec, quantized in ((got, False), (got8, True)):
            # one K and one V leaf per block (and the int8 tier's
            # scales), each where slot_state_specs, which mirrors the
            # state leaf for leaf, says: split over heads
            n_blocks = len(dec.params["blocks"])
            specs = slot_state_specs(n_blocks, quantized)
            assert jax.tree.structure(specs) \
                == jax.tree.structure(dec.state)
            names = ("k", "v") + (("k_scale", "v_scale") if quantized
                                  else ())
            for name in names:
                assert isinstance(dec.state[name], tuple)
                assert len(dec.state[name]) == n_blocks
                for leaf, spec in zip(dec.state[name], specs[name]):
                    assert not leaf.sharding.is_fully_replicated
                    assert leaf.sharding.is_equivalent_to(
                        NamedSharding(mesh, spec), leaf.ndim)

    def test_dispatch_counts_one_admit_per_bucket_group(self, model,
                                                        mesh):
        """The sharded path must keep the PR-3 dispatch economy: one
        admit dispatch per (bucket, group), one chunk dispatch per
        slot_step_many — meshes must not reintroduce per-request
        dispatches."""
        params, table = model
        ref, got = _drain_pair(params, table, mesh)
        assert got.dispatch_counts["admit"] <= \
            got.dispatch_counts["admit_requests"]
        assert got.dispatch_counts["admit"] == \
            ref.dispatch_counts["admit"]
        assert got.dispatch_counts["chunk"] == \
            ref.dispatch_counts["chunk"]

    def test_no_recompile_storm_under_mesh(self, model, mesh):
        """Per (bucket, group, layout) compile caching: driving the
        sharded decoder through SIX waves of same-bucket prompts must
        not retrace per request — at most two cache entries per
        program (the layout compile plus one committedness variant of
        the jit fastpath cache), the rest cache hits, ZERO recompile
        storms (the xla_stats counter the CI guard reads). A broken
        layout pin puts compiles at one per wave, which this bound
        catches."""
        params, table = model
        waves = 6
        tracker = get_compile_tracker()
        was_enabled = tracker.enabled
        tracker.reset()
        tracker.enabled = True
        try:
            rng = numpy.random.RandomState(5)
            dec = ContinuousDecoder(params, table, HEADS, slots=2,
                                    max_len=128, n_tokens=4, tile=8,
                                    mesh=mesh)
            for _ in range(waves):
                for _ in range(2):
                    dec.submit(rng.randint(0, VOCAB, 6))
                dec.run_until_drained(chunk=4)
            snap = tracker.snapshot()
        finally:
            tracker.reset()
            tracker.enabled = was_enabled
        assert sum(snap["storms"].values()) == 0
        for program in ("decode.admit", "decode.dispatch"):
            compiles = snap["compiles"].get(program, 0)
            hits = snap["hits"].get(program, 0)
            assert compiles <= 2, \
                "%s retraced %d times over %d same-shape waves" % (
                    program, compiles, waves)
            assert hits >= waves - 2, \
                "%s only hit %d times" % (program, hits)

    def test_rejects_indivisible_heads(self, model):
        params, table = model  # heads=8: a 3-way axis cannot divide
        mesh3 = build_mesh(devices=jax.devices()[:3], data=1, model=3)
        with pytest.raises(ValueError, match="divisible"):
            ContinuousDecoder(params, table, HEADS, mesh=mesh3)

    def test_generate_api_serves_sharded_over_http(self, model, mesh):
        """GenerateAPI(mesh=...) — the --serve-mesh surface — answers
        HTTP requests from the sharded engine with the same tokens the
        single-chip decoder streams."""
        import json
        import urllib.request

        from veles_tpu.serving import GenerateAPI

        params, table = model
        rng = numpy.random.RandomState(11)
        prompts = [rng.randint(0, VOCAB, n).tolist() for n in (6, 9)]
        ref = ContinuousDecoder(params, table, HEADS, slots=2,
                                max_len=64, n_tokens=5)
        for p in prompts:
            ref.submit(p)
        ref.run_until_drained(chunk=4)
        api = GenerateAPI(params, table, HEADS, slots=2, max_len=64,
                          n_tokens=5, chunk=4, mesh=mesh).start()
        try:
            url = "http://127.0.0.1:%d/generate" % api.port
            for rid, prompt in enumerate(prompts):
                req = urllib.request.Request(
                    url, data=json.dumps({"tokens": prompt}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=120) as resp:
                    tokens = json.loads(resp.read().decode())["tokens"]
                assert tokens == ref.results[rid]
        finally:
            api.stop()

    def test_serve_mesh_config_string(self, model):
        """build_serve_mesh: the --serve-mesh syntax builds a mesh;
        bad axes fail naming the flag; empty means single-chip."""
        mesh = build_serve_mesh("model=8")
        assert dict(mesh.shape)["model"] == 8
        assert build_serve_mesh(None) is None
        assert build_serve_mesh("") is None
        with pytest.raises(ValueError, match="serve-mesh"):
            build_serve_mesh("bogus=2")
        with pytest.raises(ValueError, match="serve-mesh"):
            build_serve_mesh("model=x")
        # the device-count product check must ALSO blame the serve
        # knob, not the training mesh config it doesn't read
        with pytest.raises(ValueError, match="serve.mesh"):
            build_serve_mesh("model=3")

    def test_serve_mesh_ignores_training_mesh_config(self, model):
        """A pod-training root.common.mesh.axes must never leak into
        the serving mesh — --serve-mesh model=8 with a training data=2
        set would otherwise build data2.model8 (16 devices) and blame
        the serve flag, or silently replicate the slot engine over the
        data axis."""
        from veles_tpu.core.config import root

        root.common.mesh.axes.data = 2
        try:
            mesh = build_serve_mesh("model=8")
            assert dict(mesh.shape)["model"] == 8
            assert dict(mesh.shape)["data"] == 1
        finally:
            root.common.mesh.axes.data = 1


class TestMeshHygiene:
    def test_build_mesh_clear_errors(self):
        with pytest.raises(ValueError, match="mesh.axes"):
            build_mesh(devices=jax.devices()[:8], data=0)
        with pytest.raises(ValueError, match="unknown mesh axis"):
            build_mesh(devices=jax.devices()[:8], bogus=2)
        with pytest.raises(ValueError, match="mesh.axes"):
            build_mesh(devices=jax.devices()[:8], data="two")
        with pytest.raises(ValueError, match="8 devices"):
            build_mesh(devices=jax.devices()[:8], data=3)

    def test_mesh_shape_on_metrics_and_dashboard(self):
        """The active mesh shape must surface on /metrics
        (veles_mesh_axis_size) and in the web-status device cell."""
        from veles_tpu.observe.xla_stats import (device_summary,
                                                 format_device_stats,
                                                 publish_device_stats)

        build_mesh(devices=jax.devices()[:8], data=2, model=4)
        registry = MetricsRegistry(enabled=True)
        publish_device_stats(registry)
        text = registry.expose()
        assert 'veles_mesh_axis_size{axis="data"} 2' in text
        assert 'veles_mesh_axis_size{axis="model"} 4' in text
        assert "veles_mesh_devices 8" in text
        summary = device_summary()
        assert summary["mesh"] == "data2.model4"
        assert "mesh data2.model4" in format_device_stats(summary)

    def test_fleet_metric_rows_carry_mesh_coordinates(self):
        from veles_tpu.parallel.mesh import mesh_coordinate_labels

        build_mesh(devices=jax.devices()[:8], data=2, model=4)
        labels = mesh_coordinate_labels()
        assert labels["mesh"] == "data2.model4"
        assert labels["process"] == "0"


class TestTrainServeTransition:
    def test_train_dp_reshard_serve_tp(self, mesh):
        """The tentpole composite: ONE checkpoint trains data-parallel
        under the mesh, reshards to the serving layout through the
        measured collective schedule, and serves tensor-parallel —
        streaming the same tokens as a single-chip decoder fed the
        gathered post-training params (no host round trip between the
        layouts)."""
        from veles_tpu.parallel import reshard as rs
        from veles_tpu.parallel.decode import slot_param_specs
        from veles_tpu.parallel.transformer_step import (
            build_transformer_train_step, shard_tokens)

        rng = numpy.random.RandomState(7)
        params = init_transformer_params(rng, BLOCKS, EMBED, HEADS,
                                         VOCAB)
        table = jnp.asarray(
            rng.randn(VOCAB, EMBED).astype(numpy.float32) * 0.3)
        train_mesh = build_mesh(devices=jax.devices()[:8], data=2,
                                model=4)
        step = build_transformer_train_step(HEADS, mesh=train_mesh,
                                            learning_rate=0.05)
        x = jnp.asarray(rng.randn(4, 8, EMBED).astype(numpy.float32))
        labels = jnp.asarray(rng.randint(0, VOCAB, (4, 8)))
        x, labels = shard_tokens((x, labels), train_mesh)
        for _ in range(3):
            params, (loss, _) = step(params, x, labels)
        # train layout (replicated) -> serve layout (TP on "model"):
        # the transition is the measured reshard, not a host gather
        served, stats = rs.reshard(
            params, train_mesh, slot_param_specs(params, "model"),
            label="train_to_serve")
        assert stats["bytes"] == 0  # replicated -> sharded: slices
        single = jax.tree.map(lambda a: jnp.asarray(numpy.asarray(a)),
                              params)
        prompts = [rng.randint(0, VOCAB, n) for n in (5, 8, 3)]
        dec_tp = ContinuousDecoder(served, table, HEADS, slots=2,
                                   max_len=64, n_tokens=5,
                                   mesh=train_mesh)
        dec_one = ContinuousDecoder(single, table, HEADS, slots=2,
                                    max_len=64, n_tokens=5)
        for p in prompts:
            dec_tp.submit(p)
            dec_one.submit(p)
        dec_tp.run_until_drained(chunk=4)
        dec_one.run_until_drained(chunk=4)
        assert dec_tp.results == dec_one.results
        # ...and back: serve -> train round-trips the params exactly
        back, stats_back = rs.reshard(served, train_mesh, P(),
                                      label="serve_to_train")
        assert stats_back["bytes"] > 0
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            numpy.testing.assert_array_equal(numpy.asarray(a),
                                             numpy.asarray(b))
