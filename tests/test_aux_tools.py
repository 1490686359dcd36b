"""Tests for the streaming loader, device benchmark, compare_snapshots
script, and the --visualize/--dump-unit-attributes CLI additions."""

import json
import threading

import numpy
import pytest

from veles_tpu.dummy import DummyLauncher, DummyWorkflow


class TestStreamLoader:
    def test_push_and_serve(self):
        from veles_tpu.loader.stream import StreamFeeder, StreamLoader

        loader = StreamLoader(DummyWorkflow(), sample_shape=(4,),
                              minibatch_size=8, secret="s3")
        loader.initialize()
        feeder = StreamFeeder("127.0.0.1:%d" % loader.port, secret="s3")
        feeder.push(numpy.arange(4.0), numpy.arange(4.0) * 2)
        loader.run()
        assert loader.minibatch_valid_size == 2
        got = numpy.asarray(loader.minibatch_data.mem)
        numpy.testing.assert_array_equal(got[0], [0, 1, 2, 3])
        numpy.testing.assert_array_equal(got[1], [0, 2, 4, 6])
        mask = numpy.asarray(loader.sample_mask.mem)
        assert mask.sum() == 2
        feeder.end()
        loader.run()
        assert bool(loader.complete)
        loader.stop()

    def test_wrong_secret_rejected(self):
        from veles_tpu.loader.stream import StreamFeeder, StreamLoader

        loader = StreamLoader(DummyWorkflow(), sample_shape=(2,),
                              minibatch_size=4, secret="right")
        loader.initialize()
        feeder = StreamFeeder("127.0.0.1:%d" % loader.port,
                              secret="wrong")
        with pytest.raises(Exception):
            feeder.push(numpy.zeros(2))
        assert loader._queue_.qsize() == 0
        loader.stop()


class TestDeviceBenchmark:
    def test_returns_positive_power(self):
        from veles_tpu.ops.benchmark import device_benchmark

        power = device_benchmark(size=128, depth=2, iters=2)
        assert power > 0
        # deterministic enough to be a balancing weight: two runs within
        # an order of magnitude
        power2 = device_benchmark(size=128, depth=2, iters=2)
        assert 0.1 < power / power2 < 10


class TestMoeRowsSweep:
    def test_one_line_per_row_count_and_the_tilings_agree(self):
        """``python -m veles_tpu.scripts.moe_rows_sweep`` at toy
        lane-aligned sizes: the CPU's times mean nothing, the shape of
        the answer and the three tilings' agreement do (the kernels
        run interpreted here). The resident kernel stops where it is
        told, the tiled one takes whole tiles only."""
        from veles_tpu.scripts.moe_rows_sweep import moe_rows_sweep

        out = moe_rows_sweep(rows=(32, 64), count=4, width=128,
                             inner=128, top_k=2, tiles=(32, 64),
                             resident_rows=32, steps=1, repeats=1)
        assert out["device"][0] == "cpu"
        assert [line["rows"] for line in out["rows"]] == [32, 64]
        kernels = (("streamed", "tiled_32"), ("tiled_32", "tiled_64"))
        for line, names in zip(out["rows"], kernels):
            want = {"rows", "touched", "grouped_ms"}
            for name in names:
                want |= {name + "_ms", name + "_gb_per_s", name + "_gap",
                         name + "_tflops"}
            assert set(line) == want
            assert line["touched"] == 4
            # tests/test_moe_streamed.py's bound, 2% of the widest
            # value, which is 2.8 and 3.1 at these sizes and seeds
            for name in names:
                assert line[name + "_gap"] < 0.02 * 2.5, line

    def test_the_widths_are_arguments(self, capsys):
        """The command line at another model's shape: one JSON line."""
        import json

        from veles_tpu.scripts import moe_rows_sweep

        moe_rows_sweep.main(
            "--count 2 --width 128 --inner 256 --top-k 1 --rows 32 "
            "--tiles 16 --resident-rows 0".split())
        out = json.loads(capsys.readouterr().out)
        assert (out["count"], out["inner"], out["top_k"]) == (2, 256, 1)
        assert "tiled_16_ms" in out["rows"][0]
        assert "streamed_ms" not in out["rows"][0]


class TestCompareSnapshots:
    def test_identical_and_diverged(self, tmp_path):
        from veles_tpu.models.mlp import MLPWorkflow
        from veles_tpu.scripts.compare_snapshots import compare
        from veles_tpu.snapshotter import Snapshotter, SnapshotterToFile

        rng = numpy.random.RandomState(0)
        X = rng.rand(60, 6).astype(numpy.float32)
        y = (X[:, 0] > 0.5).astype(numpy.int32)

        def build(epochs):
            wf = MLPWorkflow(
                DummyLauncher(), layers=(6, 2),
                loader_kwargs=dict(data=X, labels=y,
                                   class_lengths=[0, 20, 40],
                                   minibatch_size=20),
                learning_rate=0.5, max_epochs=epochs, name="cmp")
            wf.initialize()
            wf.run()
            return wf

        wf_a = build(1)
        wf_b = build(3)
        report = compare(wf_a, wf_a)
        assert report["identical"]
        report = compare(wf_a, wf_b)
        assert not report["identical"]
        assert any("weights" in k for k in report["array_diffs"])

    def test_cli(self, tmp_path):
        from veles_tpu.dummy import DummyWorkflow as DW  # noqa: F401
        from veles_tpu.models.mlp import MLPWorkflow
        from veles_tpu.scripts.compare_snapshots import main
        from veles_tpu.snapshotter import Snapshotter

        rng = numpy.random.RandomState(0)
        X = rng.rand(40, 4).astype(numpy.float32)
        y = (X[:, 0] > 0.5).astype(numpy.int32)
        wf = MLPWorkflow(
            DummyLauncher(), layers=(4, 2),
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 10, 30],
                               minibatch_size=10),
            learning_rate=0.5, max_epochs=1, name="cli-cmp")
        snap = Snapshotter(wf, prefix="cmp", directory=str(tmp_path),
                           interval=1, time_interval=0)
        wf.initialize()
        snap.initialize()
        wf.run()
        snap.run()
        path = snap.destination
        assert main([path, path]) == 0  # identical with itself


class TestFrontendGenerator:
    def test_generates_form(self, tmp_path):
        from veles_tpu.scripts.generate_frontend import generate

        path = generate(str(tmp_path / "frontend.html"))
        html = open(path).read()
        assert "--listen" in html and "--optimize" in html
        assert "command-line composer" in html
        assert 'data-flag="--seed"' in html


class TestStandardPlotters:
    def test_add_standard_plotters(self, tmp_path, monkeypatch):
        pytest.importorskip("matplotlib")
        from veles_tpu.core.config import root
        from veles_tpu.models.standard import StandardWorkflow
        from veles_tpu.plotting import GraphicsServer

        monkeypatch.setattr(root.common.disable, "plotting", False,
                            raising=False)
        rng = numpy.random.RandomState(0)
        X = rng.rand(60, 6).astype(numpy.float32)
        y = (X[:, 0] > 0.5).astype(numpy.int32)
        wf = StandardWorkflow(
            DummyLauncher(),
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 20, 40],
                               minibatch_size=20),
            layers=[{"type": "all2all_tanh", "output_sample_shape": 8},
                    {"type": "softmax", "output_sample_shape": 2}],
            learning_rate=0.5, fused=False,
            decision_kwargs=dict(max_epochs=3), name="plotted")
        plotters = wf.add_standard_plotters(weights=True)
        assert len(plotters) == 3
        gs = GraphicsServer(backend="file", directory=str(tmp_path))
        for p in plotters:
            p.graphics_server = gs
            p.redraw_threshold = 0
        wf.initialize()
        wf.run()
        gs.flush()
        rendered = gs.rendered
        gs.shutdown()
        assert any("validation errors" in name for name in rendered)
        assert any("confusion" in name for name in rendered)
        # regression: the decision freezes per-epoch snapshots BEFORE
        # resetting its accumulators — the error plotter must record the
        # REAL count, and the confusion must cover the WHOLE valid sweep
        err = plotters[0]
        assert err.values, "no plotter firings recorded"
        assert all(float(v).is_integer() and v >= 0 for v in err.values)
        cm = wf.decision.last_epoch_confusion
        assert cm is not None and int(cm.sum()) == 20  # all VALID rows


class TestCLIIntrospection:
    @pytest.fixture
    def wf_file(self, tmp_path):
        p = tmp_path / "wf.py"
        p.write_text("""
import numpy
from veles_tpu.models.mlp import MLPWorkflow

def run(load, main):
    rng = numpy.random.RandomState(0)
    X = rng.rand(40, 4).astype(numpy.float32)
    y = (X[:, 0] > 0.5).astype(numpy.int32)
    load(MLPWorkflow, layers=(4, 2),
         loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 10, 30],
                            minibatch_size=10),
         learning_rate=0.5, max_epochs=1)
    main()
""")
        return str(p)

    def test_visualize_writes_dot(self, tmp_path, wf_file):
        from veles_tpu.__main__ import main

        dot = str(tmp_path / "graph.dot")
        assert main([wf_file, "-", "--dry-run", "init",
                     "--visualize", dot]) == 0
        text = open(dot).read()
        assert text.startswith("digraph")
        assert "FullBatchLoader" in text

    def test_dump_unit_attributes(self, capsys, wf_file):
        from veles_tpu.__main__ import main

        assert main([wf_file, "-", "--dry-run", "init",
                     "--dump-unit-attributes"]) == 0
        out = capsys.readouterr().out
        lines = [json.loads(l) for l in out.splitlines()
                 if l.startswith("{")]
        names = {entry["unit"] for entry in lines}
        assert any("Loader" in entry["type"] for entry in lines)
        assert len(names) >= 5


class TestBBoxer:
    """The bounding-box labeling tool (reference scripts/bboxer.py):
    discovery, selection save/load, path containment."""

    @pytest.fixture
    def served(self, tmp_path):
        import numpy
        from PIL import Image
        from veles_tpu.scripts.bboxer import serve

        (tmp_path / "sub").mkdir()
        for rel in ("a.png", "sub/b.png"):
            arr = numpy.zeros((10, 10, 3), numpy.uint8)
            Image.fromarray(arr).save(str(tmp_path / rel))
        (tmp_path / "notes.txt").write_text("not an image")
        server = serve(str(tmp_path), port=0, block=False)
        yield "http://127.0.0.1:%d" % server.server_port, tmp_path
        server.shutdown()

    def test_list_save_roundtrip(self, served):
        import json
        import urllib.request

        base, tree = served
        with urllib.request.urlopen(base + "/list") as resp:
            items = json.loads(resp.read())
        assert [i["path"] for i in items] == ["a.png", "sub/b.png"]
        assert not any(i["labeled"] for i in items)
        boxes = [{"x": 1, "y": 2, "width": 3, "height": 4,
                  "label": "cat"}]
        req = urllib.request.Request(
            base + "/selections",
            data=json.dumps({"path": "sub/b.png",
                             "bboxes": boxes}).encode(),
            method="POST")
        with urllib.request.urlopen(req) as resp:
            assert json.loads(resp.read())["saved"] == "sub/b.png"
        sidecar = tree / "sub" / "b.png.json"
        assert json.loads(sidecar.read_text())["bboxes"] == boxes
        with urllib.request.urlopen(base + "/selections/sub/b.png") as r:
            assert json.loads(r.read())["bboxes"] == boxes
        with urllib.request.urlopen(base + "/list") as resp:
            items = {i["path"]: i["labeled"]
                     for i in json.loads(resp.read())}
        assert items == {"a.png": False, "sub/b.png": True}

    def test_path_containment(self, served):
        import json
        import urllib.error
        import urllib.request

        base, tree = served
        (tree.parent / "outside.png").write_bytes(b"x")
        req = urllib.request.Request(
            base + "/selections",
            data=json.dumps({"path": "../outside.png",
                             "bboxes": []}).encode(),
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req)
        assert err.value.code == 404
        assert not (tree.parent / "outside.png.json").exists()


class TestManhole:
    """core/manhole.py — the --manhole live debug console."""

    def _drain_until(self, sock, marker, limit=65536):
        data = b""
        while marker not in data and len(data) < limit:
            chunk = sock.recv(4096)
            if not chunk:
                break
            data += chunk
        return data

    def test_attach_eval_detach(self, tmp_path):
        import socket

        from veles_tpu.core.manhole import Manhole

        sentinel = {"value": 41}
        path = str(tmp_path / "mh.sock")
        manhole = Manhole(namespace={"sentinel": sentinel},
                          path=path).start()
        try:
            client = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            client.settimeout(10)
            client.connect(path)
            self._drain_until(client, b">>> ")
            # expression result printing + LIVE mutation of process state
            client.sendall(b"print(sentinel['value'] + 1)\n")
            out = self._drain_until(client, b">>> ")
            assert b"42" in out
            client.sendall(b"sentinel['value'] = 100\n")
            self._drain_until(client, b">>> ")
            # multi-line block compiles incrementally (the "... " prompt)
            client.sendall(b"for i in range(2):\n")
            out = self._drain_until(client, b"... ")
            client.sendall(b"    print('x%d' % i)\n\n")
            out = self._drain_until(client, b">>> ")
            assert b"x0" in out and b"x1" in out
            # errors are reported, connection survives
            client.sendall(b"1/0\n")
            out = self._drain_until(client, b">>> ")
            assert b"ZeroDivisionError" in out
            client.sendall(b"exit\n")
            out = self._drain_until(client, b"detached")
            assert b"detached" in out
            client.close()
            assert sentinel["value"] == 100  # the process really mutated
            # a SECOND connection is served after the first detaches
            client2 = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            client2.settimeout(10)
            client2.connect(path)
            self._drain_until(client2, b">>> ")
            client2.sendall(b"print(sentinel['value'])\n")
            assert b"100" in self._drain_until(client2, b">>> ")
            client2.close()
        finally:
            manhole.stop()

    def test_socket_permissions(self, tmp_path):
        import os
        import stat

        from veles_tpu.core.manhole import Manhole

        path = str(tmp_path / "mh.sock")
        manhole = Manhole(path=path).start()
        try:
            mode = stat.S_IMODE(os.stat(path).st_mode)
            assert mode == 0o600
        finally:
            manhole.stop()
        assert not os.path.exists(path)

    def test_restart_after_stop(self, tmp_path):
        """stop() then start() must serve again (regression: _closing
        stayed True, the fresh serve loop exited instantly and clients
        hung on the kernel backlog forever)."""
        import socket

        from veles_tpu.core.manhole import Manhole

        path = str(tmp_path / "mh.sock")
        manhole = Manhole(namespace={"x": 7}, path=path)
        manhole.start()
        manhole.stop()
        manhole.start()
        try:
            client = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            client.settimeout(10)
            client.connect(path)
            self._drain_until(client, b">>> ")
            client.sendall(b"print(x * 6)\n")
            assert b"42" in self._drain_until(client, b">>> ")
            client.close()
        finally:
            manhole.stop()


class TestPluginScan:
    """veles_tpu.scan_plugins(): the reference's ``veles.__plugins__``
    namespace scan (``__init__.py:191-215``) in its TPU-era form —
    installed ``veles_tpu_*`` modules are imported and their units
    register through the same metaclass registry as in-tree units."""

    def test_scans_and_registers(self, tmp_path, monkeypatch):
        import sys
        import veles_tpu
        from veles_tpu.core.registry import UnitRegistry

        plugin = tmp_path / "veles_tpu_demo_plugin.py"
        plugin.write_text(
            "from veles_tpu.core.units import TrivialUnit\n"
            "class DemoPluginUnit(TrivialUnit):\n"
            "    pass\n")
        monkeypatch.syspath_prepend(str(tmp_path))
        monkeypatch.setattr(veles_tpu, "__plugins__", None)
        plugins = veles_tpu.scan_plugins()
        names = [p.__name__ for p in plugins]
        assert "veles_tpu_demo_plugin" in names
        assert any(cls.__name__ == "DemoPluginUnit"
                   for cls in UnitRegistry.units)
        # cached: a second call returns the same list without rescanning
        assert veles_tpu.scan_plugins() is plugins
        sys.modules.pop("veles_tpu_demo_plugin", None)
        monkeypatch.setattr(veles_tpu, "__plugins__", None)


class TestYarnDiscovery:
    """yarn:// node specs resolve through the ResourceManager REST API
    (reference YARN discovery, launcher.py:887-906)."""

    def _serve(self, payload, status=200):
        import http.server
        import threading

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                assert self.path.startswith("/ws/v1/cluster/nodes")
                body = payload.encode()
                self.send_response(status)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):
                pass

        server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        return server

    def test_discovers_running_nodes(self):
        import json as jsonlib

        from veles_tpu.launcher import discover_yarn_nodes

        payload = jsonlib.dumps({"nodes": {"node": [
            {"nodeHostName": "worker-1", "state": "RUNNING"},
            {"nodeHostName": "worker-2", "state": "RUNNING"},
            {"rack": "/default", "state": "RUNNING"},  # no hostname
        ]}})
        server = self._serve(payload)
        try:
            hosts = discover_yarn_nodes(
                "127.0.0.1:%d" % server.server_address[1])
            assert hosts == ["worker-1", "worker-2"]
        finally:
            server.shutdown()

    def test_expand_mixes_plain_and_yarn_and_survives_failure(self):
        import json as jsonlib

        from veles_tpu.launcher import Launcher

        launcher = Launcher()
        payload = jsonlib.dumps({"nodes": {"node": [
            {"nodeHostName": "w1"}]}})
        server = self._serve(payload)
        try:
            specs = ["hostA",
                     "yarn://127.0.0.1:%d" % server.server_address[1],
                     "yarn://127.0.0.1:1"]  # refused: must skip, not die
            assert launcher._expand_node_specs(specs) == ["hostA", "w1"]
        finally:
            server.shutdown()
