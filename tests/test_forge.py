"""Forge model-hub tests (reference test_forge_client/server.py roles)."""

import io
import json
import os
import tarfile
import urllib.error

import numpy
import pytest

from veles_tpu.forge import ForgeClient, ForgeServer, package as pkg


def make_model_dir(tmp_path, name="toy-model", version="1.0"):
    d = tmp_path / name
    d.mkdir(parents=True)
    (d / "manifest.json").write_text(json.dumps({
        "name": name, "version": version,
        "short_description": "toy model",
        "workflow": "wf.py", "config": "cfg.py",
        "requires": ["numpy"]}))
    (d / "wf.py").write_text("""
import numpy
from veles_tpu.models.mlp import MLPWorkflow

def run(load, main):
    rng = numpy.random.RandomState(0)
    X = rng.rand(60, 6).astype(numpy.float32)
    y = (X[:, 0] > 0.5).astype(numpy.int32)
    load(MLPWorkflow, layers=(6, 2),
         loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 20, 40],
                            minibatch_size=20),
         learning_rate=0.5, max_epochs=2)
    main()
""")
    (d / "cfg.py").write_text("root.toy.x = 1\n")
    return str(d)


class TestPackage:
    def test_pack_unpack_roundtrip(self, tmp_path):
        d = make_model_dir(tmp_path)
        path, manifest = pkg.pack(d)
        assert manifest["name"] == "toy-model"
        with open(path, "rb") as fin:
            blob = fin.read()
        assert pkg.read_manifest(blob)["version"] == "1.0"
        dest = str(tmp_path / "out")
        pkg.unpack(blob, dest)
        assert sorted(os.listdir(dest)) == ["cfg.py", "manifest.json",
                                            "wf.py"]

    def test_manifest_validation(self):
        with pytest.raises(ValueError):
            pkg.validate_manifest({"workflow": "wf.py"})  # no name
        with pytest.raises(ValueError):
            pkg.validate_manifest({"name": "../evil", "workflow": "w"})
        with pytest.raises(ValueError):
            pkg.validate_manifest({"name": "x", "workflow": "w",
                                   "requires": ["numpy", "numpy>=1"]})
        # the version is a server path component AND a deploy/SLO
        # identity: reject traversal-shaped versions at pack time
        with pytest.raises(ValueError, match="version"):
            pkg.validate_manifest({"name": "x", "workflow": "w",
                                   "version": "../2.0"})

    def test_deploy_version_identity(self):
        """``deploy_version`` is the string rollouts/incidents stamp —
        name@version, server-default 1.0 when the manifest omits it."""
        manifest = {"name": "toy-model", "workflow": "w.py",
                    "version": "2.0"}
        assert pkg.deploy_version(manifest) == "toy-model@2.0"
        assert pkg.deploy_version({"name": "toy-model",
                                   "workflow": "w.py"}) == "toy-model@1.0"
        with pytest.raises(ValueError, match="version"):
            pkg.deploy_version({"name": "toy-model", "workflow": "w.py",
                                "version": "v 2"})

    def test_unpack_rejects_traversal(self, tmp_path):
        buf = io.BytesIO()
        with tarfile.open(fileobj=buf, mode="w:gz") as tar:
            manifest = json.dumps({"name": "evil",
                                   "workflow": "w.py"}).encode()
            info = tarfile.TarInfo("manifest.json")
            info.size = len(manifest)
            tar.addfile(info, io.BytesIO(manifest))
            payload = b"boom"
            info = tarfile.TarInfo("../escape.txt")
            info.size = len(payload)
            tar.addfile(info, io.BytesIO(payload))
        with pytest.raises(ValueError, match="unsafe"):
            pkg.unpack(buf.getvalue(), str(tmp_path / "dest"))
        assert not (tmp_path / "escape.txt").exists()


class TestForgeRoundtrip:
    @pytest.fixture
    def server(self, tmp_path):
        srv = ForgeServer(str(tmp_path / "store"), token="sekrit")
        srv.start()
        yield srv
        srv.stop()

    def client(self, server, token="sekrit"):
        return ForgeClient("http://127.0.0.1:%d" % server.port,
                           token=token)

    def test_upload_list_details_fetch_delete(self, server, tmp_path):
        client = self.client(server)
        result = client.upload(make_model_dir(tmp_path))
        assert result == {"name": "toy-model", "version": "1.0"}
        listing = client.list()
        assert [m["name"] for m in listing] == ["toy-model"]
        details = client.details("toy-model")
        assert details["latest"] == "1.0"
        assert details["versions"]["1.0"]["workflow"] == "wf.py"
        dest, manifest = client.fetch(
            "toy-model", dest=str(tmp_path / "fetched"))
        assert manifest["name"] == "toy-model"
        assert os.path.isfile(os.path.join(dest, "wf.py"))
        assert client.delete("toy-model") == {"deleted": True}
        assert client.list() == []

    def test_versioning(self, server, tmp_path):
        client = self.client(server)
        client.upload(make_model_dir(tmp_path, version="1.0"))
        d2 = make_model_dir(tmp_path / "v2", version="2.0")
        client.upload(d2)
        assert client.details("toy-model")["latest"] == "2.0"
        # duplicate version rejected
        with pytest.raises(urllib.error.HTTPError) as err:
            client.upload(make_model_dir(tmp_path / "dup", version="2.0"))
        assert err.value.code == 400
        # fetch a pinned old version
        dest, _ = client.fetch("toy-model", version="1.0",
                               dest=str(tmp_path / "old"))
        assert os.path.isdir(dest)

    def test_version_traversal_rejected(self, server, tmp_path):
        # regression: version strings are filesystem path components
        client = self.client(server)
        client.upload(make_model_dir(tmp_path))
        with pytest.raises(urllib.error.HTTPError) as err:
            client.fetch("toy-model", version="../../etc/passwd",
                         dest=str(tmp_path / "x"))
        assert err.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as err:
            client.upload(make_model_dir(tmp_path / "t2"),
                          version="../../../tmp/evil")
        assert err.value.code == 400

    def test_malformed_upload_gets_400(self, server):
        # regression: junk bytes must 400, not crash the handler
        import urllib.request
        req = urllib.request.Request(
            "http://127.0.0.1:%d/upload" % server.port,
            data=b"this is not a tarball",
            headers={"X-Forge-Token": "sekrit",
                     "Content-Type": "application/octet-stream"})
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 400

    def test_oversized_register_gets_single_413(self, server):
        """The shared read_body cap applies to forge's JSON endpoints:
        an oversized /register body answers ONE 413 (not a 413 followed
        by a 400 on the same socket) before buffering anything; uploads
        keep their own much larger bound (UPLOAD_MAX_BODY)."""
        import socket

        with socket.create_connection(("127.0.0.1", server.port),
                                      timeout=10) as sock:
            sock.sendall(b"POST /register HTTP/1.1\r\nHost: x\r\n"
                         b"Content-Type: application/json\r\n"
                         b"Content-Length: 9999999999\r\n\r\n")
            sock.settimeout(10)
            chunks = []
            while True:
                data = sock.recv(4096)
                if not data:
                    break
                chunks.append(data)
        reply = b"".join(chunks).decode(errors="replace")
        assert "413" in reply.split("\r\n")[0]
        assert reply.count("HTTP/1.0") == 1  # exactly one response
        # the server keeps serving afterwards
        import urllib.request
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/service?query=list" % server.port,
                timeout=10) as resp:
            assert resp.status == 200

    def test_write_actions_need_token(self, server, tmp_path):
        anon = self.client(server, token=None)
        with pytest.raises(urllib.error.HTTPError) as err:
            anon.upload(make_model_dir(tmp_path))
        assert err.value.code == 403
        # reads are open
        assert anon.list() == []

    def test_history_and_diff(self, server, tmp_path):
        """upload twice -> history lists both versions
        chronologically -> fetch either -> diff reports the manifest
        and file-content changes between them (the reference's git-tag
        history, forge_server.py:103-440)."""
        client = self.client(server)
        client.upload(make_model_dir(tmp_path, version="1.0"))
        d2 = make_model_dir(tmp_path / "v2", version="2.0")
        # change a file and add one in 2.0
        with open(os.path.join(d2, "cfg.py"), "w") as fout:
            fout.write("root.toy.x = 2\n")
        with open(os.path.join(d2, "README.md"), "w") as fout:
            fout.write("new in 2.0\n")
        client.upload(d2)

        hist = client.history("toy-model")
        assert hist["latest"] == "2.0"
        assert [h["version"] for h in hist["history"]] == ["1.0", "2.0"]
        assert all(h["uploaded"] for h in hist["history"])
        assert hist["history"][0]["uploaded_by"] == "master"

        for version in ("1.0", "2.0"):
            dest, manifest = client.fetch(
                "toy-model", version=version,
                dest=str(tmp_path / ("f" + version)))
            assert manifest["version"] == version

        delta = client.diff("toy-model", "1.0", "2.0")
        assert delta["files"]["added"] == ["README.md"]
        assert "cfg.py" in delta["files"]["changed"]
        assert "wf.py" not in delta["files"]["changed"]
        assert delta["manifest"]["changed"] == ["version"]
        # unknown version 404s
        with pytest.raises(urllib.error.HTTPError) as err:
            client.diff("toy-model", "1.0", "9.9")
        assert err.value.code == 404

    def test_register_issues_working_token(self, server, tmp_path):
        """Registration flow: /register issues a token that authorizes
        uploads, and the version records the registered email."""
        anon = self.client(server, token=None)
        with pytest.raises(urllib.error.HTTPError):
            anon.upload(make_model_dir(tmp_path / "denied"))
        issued = anon.register("dev@example.com")
        assert issued["email"] == "dev@example.com"
        registered = self.client(server, token=issued["token"])
        registered.upload(make_model_dir(tmp_path))
        hist = registered.history("toy-model")
        assert hist["history"][0]["uploaded_by"] == "dev@example.com"
        # garbage email rejected
        with pytest.raises(urllib.error.HTTPError) as err:
            anon.register("not-an-email")
        assert err.value.code == 400
        # a registered token must NOT authorize deletes — destructive
        # actions stay behind the master token
        with pytest.raises(urllib.error.HTTPError) as err:
            registered.delete("toy-model")
        assert err.value.code == 403
        # ...nor may ANOTHER registered identity add versions to a
        # model it doesn't own (hijacking "latest" of someone else's
        # model); the owner and the master token still can
        other = self.client(
            server, token=anon.register("eve@example.com")["token"])
        d2 = make_model_dir(tmp_path / "hijack", version="9.9")
        with pytest.raises(urllib.error.HTTPError) as err:
            other.upload(d2)
        assert err.value.code == 403
        registered.upload(make_model_dir(tmp_path / "own2",
                                         version="2.0"))
        self.client(server).upload(make_model_dir(tmp_path / "master3",
                                                  version="3.0"))
        assert self.client(server).delete("toy-model")["deleted"]

    def test_legacy_store_owner_seeded_from_history(self, server,
                                                    tmp_path):
        """A meta.json written before the ownership feature (no
        'owner' key) must seed the owner from the recorded uploader
        history — NOT let the next registered uploader claim it."""
        client = self.client(server)
        client.upload(make_model_dir(tmp_path))
        # simulate a pre-ownership store
        meta_path = os.path.join(server.root_dir, "toy-model",
                                 "meta.json")
        meta = json.load(open(meta_path))
        del meta["owner"]
        json.dump(meta, open(meta_path, "w"))

        anon = self.client(server, token=None)
        eve = self.client(
            server, token=anon.register("eve@example.com")["token"])
        with pytest.raises(urllib.error.HTTPError) as err:
            eve.upload(make_model_dir(tmp_path / "legacy-hijack",
                                      version="9.0"))
        assert err.value.code == 403
        # the historical uploader (the master token) still can
        client.upload(make_model_dir(tmp_path / "legit",
                                     version="2.0"))
        assert json.load(open(meta_path))["owner"] == "master"

    def test_fetched_model_runs(self, server, tmp_path):
        """The full hub story: upload, fetch, run the fetched workflow."""
        import veles_tpu
        client = self.client(server)
        client.upload(make_model_dir(tmp_path))
        dest, manifest = client.fetch("toy-model",
                                      dest=str(tmp_path / "run"))
        launcher = veles_tpu(os.path.join(dest, manifest["workflow"]),
                             os.path.join(dest, manifest["config"]))
        assert launcher.workflow.decision.epochs_done >= 2
        from veles_tpu.core.config import root
        assert root.toy.x == 1
