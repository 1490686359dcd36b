"""AOT compiled-program artifacts (docs/aot_artifacts.md): bundles of
jax.export'd StableHLO must reload with ZERO retracing and serve
bit-identical tokens — dense + paged, bf16 + int8-KV, single-chip and
the 8-device CPU mesh — behind a strict compatibility gate that refuses
stale artifacts by field name and falls back to live compilation.
`make aot` runs this file standalone."""

import hashlib
import io
import json
import os
import tarfile
import urllib.request

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.aot.artifact import (BundleBuilder, build_serving_bundle,
                                    capture_tick_programs,
                                    inspect_bundle, read_bundle)
from veles_tpu.aot.loader import (AotCompatError, check_compat,
                                  install_fused_tick, load_bundle)
from veles_tpu.observe.xla_stats import get_compile_tracker
from veles_tpu.parallel.transformer_step import init_transformer_params
from veles_tpu.serving import ContinuousDecoder, GenerateAPI

pytestmark = pytest.mark.aot

HEADS, EMBED, BLOCKS, VOCAB = 4, 16, 2, 32
#: the dense serving shape every bundle here mirrors
DENSE_KW = dict(slots=3, max_len=64, n_tokens=6, tile=16)
CHUNK = 4


@pytest.fixture(scope="module")
def model():
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, BLOCKS, EMBED, HEADS, VOCAB)
    table = jnp.asarray(
        rng.randn(VOCAB, EMBED).astype(numpy.float32) * 0.3)
    return params, table


@pytest.fixture(scope="module")
def dense_bundle(model, tmp_path_factory):
    params, table = model
    path = str(tmp_path_factory.mktemp("aot") / "dense.aot.tar")
    build_serving_bundle(params, table, HEADS, path, chunk=CHUNK,
                         **DENSE_KW)
    return path


def _prompts(n=7, seed=3):
    rng = numpy.random.RandomState(seed)
    return [rng.randint(0, VOCAB, k)
            for k in (5, 9, 3, 7, 6, 11, 4)[:n]]


def _drain(dec, prompts):
    pending = list(prompts)
    for _ in range(min(3, len(pending))):
        dec.submit(pending.pop(0))
    dec.drain_pipelined(
        CHUNK, admit=lambda: pending and dec.submit(pending.pop(0)))
    return dec


class TestBundleFormat:
    def test_sha_addressed_members_and_sidecar(self, dense_bundle):
        manifest, members = read_bundle(dense_bundle)
        assert manifest["kind"] == "veles-aot-bundle"
        for row in manifest["programs"]:
            blob = members[row["member"]]
            assert row["member"] == "programs/%s" \
                % hashlib.sha256(blob).hexdigest()
        info = inspect_bundle(dense_bundle)
        assert info["programs"] == len(manifest["programs"]) > 0
        assert os.path.isfile(dense_bundle + ".sha256")

    def test_build_twice_same_sha(self, model, tmp_path):
        """The sha-addressed store's dedup contract: two builds of the
        same configuration are byte-identical."""
        params, table = model
        digests = []
        for name in ("a.tar", "b.tar"):
            path = str(tmp_path / name)
            build_serving_bundle(params, table, HEADS, path,
                                 chunk=CHUNK, buckets=[16],
                                 **DENSE_KW)
            with open(path, "rb") as fin:
                digests.append(
                    hashlib.sha256(fin.read()).hexdigest())
        assert digests[0] == digests[1]

    def test_tampered_member_refused(self, dense_bundle, tmp_path):
        manifest, members = read_bundle(dense_bundle)
        victim = manifest["programs"][0]["member"]
        bad = str(tmp_path / "bad.tar")
        with tarfile.open(bad, "w") as tar:
            for name, blob in members.items():
                if name == victim:
                    blob = blob[:-1] + bytes([blob[-1] ^ 1])
                info = tarfile.TarInfo(name)
                info.size = len(blob)
                tar.addfile(info, io.BytesIO(blob))
        with pytest.raises(ValueError, match="content hash"):
            load_bundle(bad)


class TestCompatGate:
    """The mismatch-rejection matrix: every stale field is refused BY
    NAME — never a wrong-answer execute."""

    @pytest.fixture()
    def manifest(self, dense_bundle):
        return read_bundle(dense_bundle)[0]

    @pytest.mark.parametrize("field,value", [
        ("schema", 999),
        ("jax", "0.0.1"),
        ("jaxlib", "0.0.1"),
    ])
    def test_version_fields_refused(self, manifest, field, value):
        stale = dict(manifest)
        stale[field] = value
        with pytest.raises(AotCompatError) as err:
            check_compat(stale)
        assert err.value.field == field

    def test_fingerprint_refused(self, manifest):
        stale = dict(manifest)
        stale["fingerprint"] = dict(manifest["fingerprint"],
                                    device_kind="TPU v9000")
        with pytest.raises(AotCompatError) as err:
            check_compat(stale)
        assert err.value.field == "fingerprint"

    def test_mesh_refused_both_ways(self, manifest):
        from veles_tpu.parallel.mesh import build_mesh

        mesh = build_mesh(devices=jax.devices()[:8], data=1, model=8)
        with pytest.raises(AotCompatError) as err:
            check_compat(manifest, mesh=mesh)  # single-chip bundle
        assert err.value.field == "mesh"
        stale = dict(manifest, mesh={"axes": {"model": 2},
                                     "devices": 2})
        with pytest.raises(AotCompatError) as err:
            check_compat(stale)  # mesh bundle, no serving mesh
        assert err.value.field == "mesh"

    def test_stale_bundle_file_refused_by_name(self, dense_bundle,
                                               tmp_path):
        """End to end through load_bundle: a re-written bundle whose
        manifest records another jaxlib refuses with the field."""
        manifest, members = read_bundle(dense_bundle)
        manifest = dict(manifest, jaxlib="0.0.1")
        stale = str(tmp_path / "stale.tar")
        with tarfile.open(stale, "w") as tar:
            payload = json.dumps(manifest).encode()
            info = tarfile.TarInfo("manifest.json")
            info.size = len(payload)
            tar.addfile(info, io.BytesIO(payload))
            for name, blob in members.items():
                if name == "manifest.json":
                    continue
                info = tarfile.TarInfo(name)
                info.size = len(blob)
                tar.addfile(info, io.BytesIO(blob))
        with pytest.raises(AotCompatError) as err:
            load_bundle(stale)
        assert err.value.field == "jaxlib"

    def test_geometry_mismatch_degrades_to_live(self, model,
                                                dense_bundle):
        """A bundle for another serving shape must NOT bind — the
        decoder logs the stale field and serves via live compilation,
        bit-identical."""
        params, table = model
        aot = load_bundle(dense_bundle, prefetch=False)
        kw = dict(DENSE_KW, slots=2)  # differs from the bundle
        dec = ContinuousDecoder(params, table, HEADS, aot=aot, **kw)
        assert not dec.aot_active
        ref = ContinuousDecoder(params, table, HEADS, **kw)
        for d in (dec, ref):
            _drain(d, _prompts(3))
        assert dec.results == ref.results


    def test_stacked_slab_bundle_is_a_miss_not_an_execute(
            self, model, dense_bundle):
        """A bundle built when the slab was one stacked ``(L, ...)``
        array per name has the serving geometry of today and other
        operand avals: every program of it is refused at its first
        dispatch by aval mismatch and served live, bit-identical, and
        the manifest's operand rows say shape, dtype, spec and
        layout."""
        params, table = model
        aot = load_bundle(dense_bundle, prefetch=False)
        leaf = [DENSE_KW["slots"], EMBED, DENSE_KW["max_len"]]
        for entry in aot._entries.values():
            rows = entry.row["in_avals"]
            assert all(len(row) == 4 for row in rows)
            assert sum(row[0] == leaf for row in rows) == 2 * BLOCKS \
                or not entry.row["name"].startswith("decode.")
            # the bundle of before: K and V stacked over the blocks
            kept = [row for row in rows if row[0] != leaf]
            stacked = [[BLOCKS] + leaf, rows[0][1], "", ""]
            entry.row["in_avals"] = kept + [stacked, stacked]
        dec = ContinuousDecoder(params, table, HEADS, aot=aot,
                                **DENSE_KW)
        assert dec.aot_active           # the geometry is today's
        ref = ContinuousDecoder(params, table, HEADS, **DENSE_KW)
        for d in (dec, ref):
            _drain(d, _prompts(3))
        assert dec.results == ref.results
        stats = aot.stats()
        assert not stats["hits"] and sum(stats["misses"].values()) > 0


class TestBitIdentity:
    """AOT-loaded programs must stream EXACTLY what live-compiled ones
    do — the wire-format conversion is a bit-level reinterpretation."""

    def test_dense_streams(self, model, dense_bundle):
        params, table = model
        aot = load_bundle(dense_bundle, prefetch=False)
        ref = _drain(ContinuousDecoder(params, table, HEADS,
                                       **DENSE_KW), _prompts())
        got = _drain(ContinuousDecoder(params, table, HEADS, aot=aot,
                                       **DENSE_KW), _prompts())
        assert got.aot_active
        assert ref.results == got.results
        stats = aot.stats()
        assert sum(stats["hits"].values()) > 0
        assert not stats["misses"]
        # dispatch economy is preserved: same admit/chunk tallies
        assert ref.dispatch_counts == got.dispatch_counts

    @pytest.mark.slow
    def test_int8kv_streams(self, model, tmp_path):
        params, table = model
        kw = dict(slots=3, max_len=128, n_tokens=6, tile=128,
                  quantize="int8-kv")
        path = str(tmp_path / "int8kv.aot.tar")
        build_serving_bundle(params, table, HEADS, path, chunk=CHUNK,
                             buckets=[16, 128], **kw)
        aot = load_bundle(path, prefetch=False)
        ref = _drain(ContinuousDecoder(params, table, HEADS, **kw),
                     _prompts(4))
        got = _drain(ContinuousDecoder(params, table, HEADS, aot=aot,
                                       **kw), _prompts(4))
        assert got.aot_active
        assert ref.results == got.results
        assert not aot.stats()["misses"]

    @pytest.mark.slow
    def test_paged_streams_with_prefix_reuse(self, model, tmp_path):
        """Paged cold/hit admissions serve from the bundle; the tail
        family (unbounded key space) falls back to live compile —
        counted as a miss, still bit-identical."""
        params, table = model
        kw = dict(slots=3, max_len=64, n_tokens=6, tile=16,
                  paged=True, page_size=16)
        path = str(tmp_path / "paged.aot.tar")
        build_serving_bundle(params, table, HEADS, path, chunk=CHUNK,
                             **kw)
        aot = load_bundle(path, prefetch=False)
        rng = numpy.random.RandomState(5)
        system = rng.randint(0, VOCAB, 16)  # one whole page
        prompts = [system.tolist() + rng.randint(0, VOCAB, k).tolist()
                   for k in (3, 5, 0, 3)]
        results = []
        for a in (None, aot):
            dec = ContinuousDecoder(params, table, HEADS, aot=a, **kw)
            # sequential: later admissions hit the published prefix
            for prompt in prompts:
                rid = dec.submit(prompt)
                dec.run_until_drained(chunk=CHUNK)
            results.append((dec.results,
                            dict(dec.dispatch_counts)))
        (ref, ref_counts), (got, got_counts) = results
        assert ref == got
        assert got_counts == ref_counts
        assert got_counts["admit_hit"] > 0 \
            or got_counts["admit_tail"] > 0
        stats = aot.stats()
        assert stats["hits"].get("paged.admit", 0) > 0
        assert stats["hits"].get("paged.dispatch", 0) > 0

    @pytest.mark.slow
    def test_mesh_streams(self, tmp_path):
        """One 8-device mesh layout: the exported programs keep their
        pinned shardings and stream identically to the live sharded
        engine."""
        from veles_tpu.parallel.mesh import build_mesh

        heads, embed, vocab = 8, 32, 16
        rng = numpy.random.RandomState(0)
        params = init_transformer_params(rng, BLOCKS, embed, heads,
                                         vocab)
        table = jnp.asarray(
            rng.randn(vocab, embed).astype(numpy.float32) * 0.3)
        mesh = build_mesh(devices=jax.devices()[:8], data=1, model=8)
        kw = dict(slots=2, max_len=64, n_tokens=5, tile=16)
        path = str(tmp_path / "mesh.aot.tar")
        build_serving_bundle(params, table, heads, path, chunk=CHUNK,
                             mesh=mesh, buckets=[16], **kw)
        aot = load_bundle(path, mesh=mesh, prefetch=False)
        prompts = [rng.randint(0, vocab, k) for k in (5, 9, 3)]
        results = []
        for a in (None, aot):
            dec = ContinuousDecoder(params, table, heads, mesh=mesh,
                                    aot=a, **kw)
            _drain(dec, prompts)
            results.append(dec)
        ref, got = results
        assert got.aot_active
        assert ref.results == got.results
        assert not got.state["k"].sharding.is_fully_replicated

    def test_fused_train_step(self, tmp_path):
        """The training half of the libVeles analogue: one captured
        fused train step replays bit-identically, and an uncovered
        minibatch shape falls back to the live tick."""
        from veles_tpu.parallel import fused

        specs = [
            {"kind": "dense", "activation": "tanh",
             "leaves": fused._WB_LEAVES, "has_params": True,
             "solver": "momentum"},
            {"kind": "dense", "activation": "linear",
             "leaves": fused._WB_LEAVES, "has_params": True,
             "solver": "momentum"},
        ]
        steps = fused.build_tick(specs, "none", with_confusion=False)
        rng = numpy.random.RandomState(0)
        w1 = rng.randn(8, 6).astype("float32")
        w2 = rng.randn(6, 3).astype("float32")

        def mk_params():
            return [{"p": {"w": jnp.asarray(w1), "b": jnp.zeros(6)},
                     "v": {"w": jnp.zeros((8, 6)), "b": jnp.zeros(6)}},
                    {"p": {"w": jnp.asarray(w2), "b": jnp.zeros(3)},
                     "v": {"w": jnp.zeros((6, 3)), "b": jnp.zeros(3)}}]

        hypers = [jnp.asarray([0.1, 0.1, 0.0, 0.0, 0.9],
                              jnp.float32)] * 2
        data = jnp.asarray(rng.randn(32, 8).astype("float32"))
        labels = jnp.asarray(rng.randint(0, 3, 32), jnp.int32)
        indices = jnp.arange(8, dtype=jnp.int32)
        args = (mk_params(), hypers, {}, data, labels, indices,
                jnp.float32(8), numpy.int64(0))
        ref_params, (ref_loss, ref_err) = steps[0](
            mk_params(), hypers, {}, data, labels, indices,
            jnp.float32(8), numpy.int64(0))
        path = str(tmp_path / "tick.aot.tar")
        builder = BundleBuilder()
        capture_tick_programs(builder, steps, args)
        builder.write(path)
        aot = load_bundle(path, prefetch=False)
        install_fused_tick(aot, specs, norm_type="none",
                           with_confusion=False)
        installed = fused.build_tick(specs, "none",
                                     with_confusion=False)
        got_params, (got_loss, got_err) = installed[0](
            mk_params(), hypers, {}, data, labels, indices,
            jnp.float32(8), numpy.int64(0))
        for a, b in zip(jax.tree.leaves(ref_params),
                        jax.tree.leaves(got_params)):
            assert (numpy.asarray(a) == numpy.asarray(b)).all()
        assert float(ref_loss) == float(got_loss)
        assert int(ref_err) == int(got_err)
        assert aot.stats()["hits"].get("fused.train_step") == 1
        # odd tail minibatch: live fallback, never a wrong shape
        installed[0](mk_params(), hypers, {}, data, labels,
                     jnp.arange(5, dtype=jnp.int32), jnp.float32(5),
                     numpy.int64(0))
        assert aot.stats()["misses"].get("fused.train_step") == 1


class TestZeroRetraceServing:
    def test_compiles_flat_across_aot_warmup(self, model,
                                             dense_bundle):
        """THE acceptance gate: an AOT-booted GenerateAPI serves a
        warmup over every bucket with veles_xla_compiles_total FLAT
        for the decode programs — zero retrace proven by the
        device-truth counter, not by timing — while every dispatch
        books as an AOT hit."""
        params, table = model
        aot = load_bundle(dense_bundle, prefetch=False)
        api = GenerateAPI(params, table, HEADS, chunk=CHUNK,
                          port=0, aot=aot, **DENSE_KW).start()
        try:
            tracker = get_compile_tracker()
            before = tracker.snapshot()["compiles"]
            hits_before = sum(aot.stats()["hits"].values())
            rng = numpy.random.RandomState(7)
            url = "http://127.0.0.1:%d/generate" % api.port
            for k in (5, 9, 17, 33, 3):  # spans every prompt bucket
                req = urllib.request.Request(
                    url, data=json.dumps(
                        {"tokens":
                         rng.randint(0, VOCAB, k).tolist()}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as resp:
                    out = json.loads(resp.read().decode())
                assert out["tokens"]
            after = tracker.snapshot()["compiles"]
            for name in set(before) | set(after):
                if name.startswith(("decode.", "paged.")):
                    assert after.get(name, 0) == before.get(name, 0), \
                        "live compile of %s during AOT warmup" % name
            stats = aot.stats()
            assert sum(stats["hits"].values()) > hits_before
            assert not stats["misses"]
            # the /metrics surface carries the AOT plane
            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/metrics" % api.port,
                    timeout=10) as resp:
                text = resp.read().decode()
            assert "veles_aot_hits_total" in text
            assert "veles_aot_programs_loaded" in text
        finally:
            api.stop()

    def test_breaker_rebuild_reuses_loaded_programs(self, model,
                                                    dense_bundle):
        """A rebuilt decoder binds the SAME AotPrograms — a trip never
        pays a second deserialize, and the probe decode rides the
        loaded programs too."""
        params, table = model
        aot = load_bundle(dense_bundle, prefetch=False)
        api = GenerateAPI(params, table, HEADS, chunk=CHUNK, port=0,
                          aot=aot, **DENSE_KW)
        first = api.decoder
        assert first.aot_active and first.aot is aot
        assert api._rebuild()
        assert api.decoder is not first
        assert api.decoder.aot_active
        assert api.decoder.aot is aot

    def test_serve_aot_config_fallback(self, model, tmp_path,
                                       caplog):
        """root.common.serve.aot pointing at a stale bundle must boot
        a WORKING live-compiled server, loudly."""
        import logging

        from veles_tpu.core.config import root

        params, table = model
        stale = str(tmp_path / "missing.aot.tar")
        root.common.serve.aot = stale
        try:
            with caplog.at_level(logging.WARNING):
                api = GenerateAPI(params, table, HEADS, chunk=CHUNK,
                                  port=0, **DENSE_KW)
            assert not api.decoder.aot_active
            assert any("refused" in r.message for r in caplog.records)
        finally:
            root.common.serve.aot = None


class TestDeterministicPackages:
    """The determinism satellite: identical state must repack to
    identical bytes so sha-addressed stores dedupe."""

    def test_forge_pack_twice_same_sha(self, tmp_path):
        from test_forge import make_model_dir
        from veles_tpu.forge import package as pkg

        d = make_model_dir(tmp_path)
        digests = []
        for name in ("one.tar.gz", "two.tar.gz"):
            path, _ = pkg.pack(d, out_path=str(tmp_path / name))
            with open(path, "rb") as fin:
                digests.append(
                    hashlib.sha256(fin.read()).hexdigest())
        assert digests[0] == digests[1]

    def test_native_export_twice_same_sha(self, tmp_path):
        """export.py's package bytes: fixed member mtimes AND a fixed
        contents.json stamp (the old time.strftime path made every
        repack a new sha)."""
        import time

        from veles_tpu.dummy import DummyLauncher
        from veles_tpu.export import package_export
        from veles_tpu.models.mlp import MLPWorkflow

        rng = numpy.random.RandomState(0)
        data = rng.rand(40, 6).astype(numpy.float32)
        labels = (data[:, 0] > 0.5).astype(numpy.int32)
        wf = MLPWorkflow(
            DummyLauncher(), layers=(5, 2),
            loader_kwargs=dict(data=data, labels=labels,
                               class_lengths=[0, 10, 30],
                               minibatch_size=10))
        wf.initialize()
        digests = []
        for name in ("one.tar", "two.tar"):
            path = package_export(wf, str(tmp_path / name))
            time.sleep(0.01)  # a wall-clock stamp WOULD differ
            with open(path, "rb") as fin:
                digests.append(
                    hashlib.sha256(fin.read()).hexdigest())
        assert digests[0] == digests[1]


class TestForgeArtifactDistribution:
    """Artifact bundles ride forge packages; the server verifies the
    sha256 sidecar on receipt and 422s tampered uploads."""

    def _package_with_artifact(self, tmp_path, dense_bundle,
                               tamper=False):
        from test_forge import make_model_dir
        from veles_tpu.aot.cli import stage_into_package
        from veles_tpu.forge import package as pkg

        d = make_model_dir(tmp_path)
        stage_into_package(dense_bundle, d)
        if tamper:
            victim = os.path.join(d, os.path.basename(dense_bundle))
            with open(victim, "r+b") as fout:
                fout.seek(-1, os.SEEK_END)
                last = fout.read(1)
                fout.seek(-1, os.SEEK_END)
                fout.write(bytes([last[0] ^ 1]))
        path, manifest = pkg.pack(d, out_path=str(
            tmp_path / "pkg.tar.gz"))
        assert manifest["artifacts"] == [
            os.path.basename(dense_bundle)]
        with open(path, "rb") as fin:
            return fin.read()

    def test_upload_verifies_and_rejects_tamper(self, tmp_path,
                                                dense_bundle):
        from veles_tpu.forge import ForgeServer, package as pkg

        server = ForgeServer(str(tmp_path / "store"))
        blob = self._package_with_artifact(tmp_path, dense_bundle)
        assert server.upload(blob, version="1.0")["name"] == \
            "toy-model"
        bad = self._package_with_artifact(
            tmp_path.joinpath("t2"), dense_bundle, tamper=True)
        with pytest.raises(pkg.TamperedPackageError):
            server.upload(bad, version="1.1")
        # and over HTTP the refusal is 422, nothing stored
        server.start()
        try:
            req = urllib.request.Request(
                "http://127.0.0.1:%d/upload?version=2.0"
                % server.port, data=bad,
                headers={"Content-Type": "application/octet-stream"})
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(req, timeout=30)
            assert err.value.code == 422
            meta = server.details("toy-model")
            assert "2.0" not in meta["versions"]
        finally:
            server.stop()

    def test_fetched_bundle_loads(self, tmp_path, dense_bundle,
                                  model):
        """The full distribution loop: pack -> upload -> fetch ->
        unpack -> load_bundle -> serve."""
        from veles_tpu.forge import ForgeServer, package as pkg

        server = ForgeServer(str(tmp_path / "store"))
        blob = self._package_with_artifact(tmp_path, dense_bundle)
        server.upload(blob, version="1.0")
        fetched = server.fetch("toy-model")
        dest = str(tmp_path / "fetched")
        manifest = pkg.unpack(fetched, dest)
        bundle = os.path.join(dest, manifest["artifacts"][0])
        aot = load_bundle(bundle, prefetch=False)
        params, table = model
        dec = ContinuousDecoder(params, table, HEADS, aot=aot,
                                **DENSE_KW)
        assert dec.aot_active


class TestCli:
    def test_build_inspect_verify(self, tmp_path, capsys):
        from veles_tpu.aot.cli import main

        out = str(tmp_path / "cli.aot.tar")
        assert main(["build", "--out", out, "--blocks", "1",
                     "--embed", "16", "--heads", "4", "--vocab", "32",
                     "--slots", "2", "--max-len", "32",
                     "--n-tokens", "4", "--chunk", "2",
                     "--tile", "16"]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["programs"] > 0
        assert main(["inspect", out]) == 0
        assert main(["verify", out]) == 0
        assert "loadable" in capsys.readouterr().out
        # the operator's intended mesh participates in the verdict: a
        # single-chip bundle is NOT loadable for a model=8 boot
        assert main(["verify", out, "--mesh", "model=8"]) == 1
        assert "mesh" in capsys.readouterr().out
        # verify refuses a tampered file with exit 2
        with open(out, "r+b") as fout:
            fout.seek(-1, os.SEEK_END)
            last = fout.read(1)
            fout.seek(-1, os.SEEK_END)
            fout.write(bytes([last[0] ^ 1]))
        assert main(["verify", out]) == 2


class TestRegressDirections:
    def test_compiles_and_coldstart_keys_are_lower_better(self):
        from veles_tpu.observe.regress import compare, regressions

        old = {"coldstart_to_first_token_ms": 100.0,
               "warmup_compiles": 2}
        new = {"coldstart_to_first_token_ms": 150.0,
               "warmup_compiles": 6}
        bad = {f["key"] for f in regressions(compare(old, new))}
        assert "coldstart_to_first_token_ms" in bad
        assert "warmup_compiles" in bad
        assert not regressions(compare(old, dict(old)))
