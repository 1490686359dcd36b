"""Fleet-mode tests: master+slave in one process over loopback (the
reference's key distributed-test pattern, ``test_network.py:111-137`` /
``test_launcher.py:91-118``)."""

import asyncio
import os
import threading

import numpy
import pytest

from veles_tpu.core import prng
from veles_tpu.fleet.protocol import encode_frame, machine_id
from veles_tpu.launcher import Launcher
from veles_tpu.loader.base import VALID
from veles_tpu.models.mlp import MLPWorkflow


def _digits():
    from sklearn.datasets import load_digits
    d = load_digits()
    return (d.data.astype(numpy.float32),
            d.target.astype(numpy.int32))


def _kw(max_epochs=2, minibatch=300):
    X, y = _digits()
    return dict(
        layers=(16, 10),
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 297, 1500],
                           minibatch_size=minibatch,
                           normalization_type="linear"),
        learning_rate=0.5, max_epochs=max_epochs)


def _seed():
    prng.get("default").seed(42)
    prng.get("loader").seed(43)


def _run_master(kw):
    _seed()
    master = Launcher(listen_address="127.0.0.1:0")
    wf = MLPWorkflow(master, name="fleet-t", **kw)
    master.initialize()
    thread = threading.Thread(target=master.run, daemon=True)
    thread.start()
    return master, wf, thread


def _run_slave(port, kw, **slave_kw):
    _seed()
    slave = Launcher(master_address="127.0.0.1:%d" % port, **slave_kw)
    MLPWorkflow(slave, name="fleet-t", **kw)
    slave.initialize()
    return slave


class FakeReader:
    def __init__(self, data):
        import io
        self.buf = io.BytesIO(data)

    async def readexactly(self, n):
        data = self.buf.read(n)
        if len(data) < n:
            raise asyncio.IncompleteReadError(data, n)
        return data


KEY = b"test-secret"


class TestProtocol:
    def test_frame_roundtrip(self):
        msg = {"type": "job", "job": [numpy.arange(5), {"a": 1}]}
        frame = encode_frame(msg, KEY)
        from veles_tpu.fleet.protocol import read_frame
        out = asyncio.run(
            read_frame(FakeReader(frame), KEY))
        assert out["type"] == "job"
        numpy.testing.assert_array_equal(out["job"][0], numpy.arange(5))

    def test_big_frame_compressed(self):
        big = {"data": numpy.zeros(1024 * 1024, numpy.float32)}
        frame = encode_frame(big, KEY)
        assert len(frame) < 1024 * 1024  # gzip kicked in

    def test_unauthenticated_frame_rejected(self):
        """A frame MAC'd with the wrong key must never reach
        pickle.loads (pre-handshake RCE hardening)."""
        from veles_tpu.fleet.protocol import ProtocolError, read_frame
        frame = encode_frame({"type": "hello"}, b"attacker-key")
        with pytest.raises(ProtocolError):
            asyncio.run(
                read_frame(FakeReader(frame), KEY))

    def test_tampered_frame_rejected(self):
        from veles_tpu.fleet.protocol import ProtocolError, read_frame
        frame = bytearray(encode_frame({"type": "hello"}, KEY))
        frame[-1] ^= 0xFF
        with pytest.raises(ProtocolError):
            asyncio.run(
                read_frame(FakeReader(bytes(frame)), KEY))

    def test_secret_defaults_to_workflow_checksum(self, monkeypatch):
        from veles_tpu.core.config import root
        from veles_tpu.fleet.protocol import resolve_secret

        monkeypatch.delenv("VELES_TPU_FLEET_SECRET", raising=False)
        # root is a process-global singleton: force the unset state rather
        # than assuming no earlier test configured a secret
        monkeypatch.setattr(root.common.fleet, "secret", None, raising=False)

        class WF:
            checksum = "abc123"

        secret, source = resolve_secret(WF(), with_source=True)
        assert secret == b"abc123" and source == "checksum"

    def test_machine_id_stable(self):
        assert machine_id() == machine_id()

    @staticmethod
    def _raw_frame(codec, payload):
        """Build a frame with an arbitrary codec byte and a VALID MAC, so
        the test exercises the post-authentication rejection path."""
        import struct
        from veles_tpu.fleet.protocol import _mac
        return (struct.pack(">IB", len(payload), codec)
                + _mac(KEY, codec, payload) + payload)

    def test_gzip_bomb_rejected(self):
        """An authenticated peer must not be able to detonate a gzip bomb:
        the frame limit applies to the DECOMPRESSED size too."""
        import gzip
        from veles_tpu.fleet.protocol import ProtocolError, read_frame
        bomb = gzip.compress(b"\0" * (4 * 1024 * 1024), compresslevel=9)
        assert len(bomb) < 1024 * 1024  # fits the wire-length check
        frame = self._raw_frame(1, bomb)
        with pytest.raises(ProtocolError, match="exceeds limit"):
            asyncio.run(read_frame(FakeReader(frame), KEY,
                                   max_frame=1024 * 1024))

    def test_truncated_gzip_member_rejected(self):
        """A truncated gzip member is a protocol violation, never
        silently-partial data."""
        import gzip
        import pickle
        from veles_tpu.fleet.protocol import ProtocolError, read_frame
        member = gzip.compress(pickle.dumps({"type": "job"}))
        frame = self._raw_frame(1, member[:-6])
        with pytest.raises(ProtocolError,
                           match="gzip"):
            asyncio.run(read_frame(FakeReader(frame), KEY))

    def test_unknown_codec_byte_rejected(self):
        """An authenticated frame with an unassigned codec byte must be
        rejected before any deserialization."""
        from veles_tpu.fleet.protocol import ProtocolError, read_frame
        frame = self._raw_frame(7, b"payload")
        with pytest.raises(ProtocolError, match="unknown frame codec"):
            asyncio.run(read_frame(FakeReader(frame), KEY))

    def test_oversized_preauth_hello_rejected(self):
        """The server reads the pre-auth hello with a 64 KiB cap: an
        unauthenticated peer cannot make it buffer a giant payload."""
        from veles_tpu.fleet.protocol import ProtocolError, read_frame
        # incompressible padding: the frame must exceed the cap on the
        # wire, exercising the pre-buffer length check (a compressible
        # payload would instead trip the decompressed-size guard)
        big = encode_frame({"type": "hello",
                            "pad": os.urandom(1 << 17)}, KEY)
        with pytest.raises(ProtocolError, match="exceeds limit"):
            asyncio.run(read_frame(FakeReader(big), KEY,
                                   max_frame=1 << 16))


class TestSharedIO:
    """Same-host shared-memory data plane (reference txzmq SharedIO)."""

    def _read(self, frame):
        from veles_tpu.fleet.protocol import read_frame
        return asyncio.run(
            read_frame(FakeReader(frame), KEY))

    @staticmethod
    def _segments():
        from veles_tpu.fleet import sharedio
        return {n for n in os.listdir(sharedio.shm_dir())
                if n.startswith(sharedio._PREFIX)}

    def test_shm_frame_roundtrip(self):
        msg = {"type": "job", "job": numpy.arange(50000)}
        before = self._segments()
        frame = encode_frame(msg, KEY, shm_threshold=0)
        # only the descriptor rode the wire
        assert len(frame) < 1024
        created = self._segments() - before
        assert len(created) == 1, "no segment created"
        out = self._read(frame)
        numpy.testing.assert_array_equal(out["job"], numpy.arange(50000))
        assert not created & self._segments(), "segment not unlinked"

    def test_shm_tamper_rejected(self):
        from veles_tpu.fleet import sharedio
        from veles_tpu.fleet.protocol import ProtocolError
        before = self._segments()
        frame = encode_frame({"x": numpy.zeros(9000)}, KEY,
                             shm_threshold=0)
        name = (self._segments() - before).pop()
        path = os.path.join(sharedio.shm_dir(), name)
        with open(path, "r+b") as f:
            f.write(b"\xff")
        with pytest.raises(ProtocolError):
            self._read(frame)
        # left in place on failed verification
        assert name in self._segments()
        os.unlink(path)

    def test_shm_path_containment(self):
        """A descriptor must not be able to point outside the segment
        namespace (authenticated-peer unlink/read primitive)."""
        import pickle
        from veles_tpu.fleet.protocol import ProtocolError
        for name in ("../../etc/passwd", "/etc/passwd", "evil"):
            bad = {"__shm__": {"name": name, "size": 1, "mac": "0"}}
            frame = encode_frame(bad, KEY)
            with pytest.raises(ProtocolError):
                self._read(frame)

    def test_negotiated_on_loopback_fleet(self):
        """Same machine id -> the welcome negotiates shm; a big job
        payload moves via a segment end-to-end."""
        from veles_tpu.fleet import sharedio
        from veles_tpu.fleet.server import Server

        class BigJobWorkflow:
            checksum = "shm-test"
            applied = []

            def generate_initial_data_for_slave(self, slave):
                return None

            def generate_data_for_slave(self, slave):
                if self.applied:
                    return None
                return numpy.ones(200000, numpy.float32)  # 800KB

            def apply_data_from_slave(self, update, slave):
                self.applied.append(numpy.asarray(update).sum())

            def apply_initial_data_from_master(self, initial):
                pass

            def do_job(self, job, callback):
                callback(numpy.asarray(job) * 2)

            def drop_slave(self, slave):
                pass

            def has_more_jobs(self):
                return not self.applied

        from veles_tpu.fleet.client import Client
        wf = BigJobWorkflow()
        server = Server("127.0.0.1:0", wf, secret="shm-test").start()
        done = threading.Event()
        server.on_finished = done.set
        client = Client(server.address, BigJobWorkflow(),
                        secret="shm-test").start()
        try:
            assert done.wait(timeout=20), "fleet job did not complete"
            assert wf.applied and wf.applied[0] == 400000.0
            slave = next(iter(server.slaves.values()), None)
            assert slave is None or slave.shm_threshold is not None
        finally:
            client.stop()
            server.stop()


@pytest.mark.slow
class TestLoopback:
    def test_sync_training_and_parity(self):
        """One master + one sync slave must produce the SAME result as a
        standalone run (sequential SGD equivalence)."""
        kw = _kw()
        _seed()
        lau = Launcher()
        wf_sa = MLPWorkflow(lau, name="fleet-t", **kw)
        lau.initialize()
        lau.run()
        expected = wf_sa.decision.best_n_err[VALID]

        master, wf_m, thread = _run_master(kw)
        slave = _run_slave(master.agent.port, kw)
        slave.run()
        thread.join(60)
        assert not thread.is_alive(), "master did not finish"
        assert wf_m.decision.best_n_err[VALID] == expected
        assert slave.agent.jobs_done == 12  # 2 epochs x (1 valid + 5 train)
        master.stop()
        slave.stop()

    def test_two_slaves_share_the_epoch(self):
        kw = _kw(max_epochs=2)
        master, wf_m, thread = _run_master(kw)
        s1 = _run_slave(master.agent.port, kw)
        s2 = _run_slave(master.agent.port, kw)
        t1 = threading.Thread(target=s1.run, daemon=True)
        t1.start()
        s2.run()
        t1.join(60)
        thread.join(60)
        assert not thread.is_alive()
        total = s1.agent.jobs_done + s2.agent.jobs_done
        # the job stream is asynchronous: with 2 slaves the master may hand
        # out a couple of next-epoch jobs before the stop decision lands,
        # so the total can overshoot the 12-minibatch epoch slightly
        assert total >= 12, "jobs split %d+%d < 12" % (
            s1.agent.jobs_done, s2.agent.jobs_done)
        assert s1.agent.jobs_done > 0 and s2.agent.jobs_done > 0
        assert wf_m.decision.best_n_err[VALID] is not None
        master.stop()
        s1.stop()
        s2.stop()

    def test_n_slave_convergence_parity(self):
        """prove N-slave training converges like
        1-slave training on a real dataset (digits, 4 epochs): both must
        reach the same accuracy class."""
        kw = _kw(max_epochs=4, minibatch=300)
        results = {}
        for n_slaves in (1, 2):
            master, wf_m, thread = _run_master(kw)
            slaves = [_run_slave(master.agent.port, kw)
                      for _ in range(n_slaves)]
            threads = [threading.Thread(target=s.run, daemon=True)
                       for s in slaves]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            thread.join(120)
            assert not thread.is_alive(), "master did not finish"
            results[n_slaves] = wf_m.decision.best_n_err[VALID]
            master.stop()
            for s in slaves:
                s.stop()
        # same accuracy class: both clearly learned (digits: 297 valid
        # rows; an untrained model sits near 267 errors). The 2-slave
        # bound is intentionally loose: async stale-update overwrites
        # make the interleaving nondeterministic (observed 40-60 across
        # runs at 4 epochs); sync numerics are pinned EXACTLY by
        # test_sync_training_and_parity instead
        assert results[1] <= 40, results
        assert results[2] <= 80, results
        assert abs(results[1] - results[2]) <= 45, results

    def test_average_merge_convergence_tight(self, monkeypatch):
        """under ``merge="average"`` the blended updates
        make N-slave convergence deterministic-ish, so the bounds can be
        TIGHT (the async ``overwrite`` test above stays loose — that is
        its nature)."""
        from veles_tpu.core.config import root
        monkeypatch.setattr(root.common.fleet, "merge", "average",
                            raising=False)
        kw = _kw(max_epochs=6, minibatch=300)
        results = {}
        for n_slaves in (1, 2):
            master, wf_m, thread = _run_master(kw)
            slaves = [_run_slave(master.agent.port, kw)
                      for _ in range(n_slaves)]
            threads = [threading.Thread(target=s.run, daemon=True)
                       for s in slaves]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180)
            thread.join(180)
            assert not thread.is_alive(), "master did not finish"
            results[n_slaves] = wf_m.decision.best_n_err[VALID]
            master.stop()
            for s in slaves:
                s.stop()
        # both clearly learned (random ~267/297; absolute error trails
        # overwrite-mode because averaging against the stale master
        # state damps each step — the EASGD tradeoff) and, the point:
        # averaging makes the outcome near-independent of slave count
        # and scheduling — measured {1: 42, 2: 46-47} across repeated
        # 6-epoch runs, vs the 40-80 swing that forced the overwrite
        # test's wide bounds
        assert results[1] <= 50, results
        assert results[2] <= 60, results
        assert abs(results[1] - results[2]) <= 12, results

    def test_fleet_payload_covers_all_leaves_and_solver_state(self):
        """(1) GD payloads derive from the unit's slot
        contract — GDSelfAttention's out projection rides them (it
        silently desynchronized before); (2) stateful solvers ship
        moments + step both ways; momentum stays weights-only
        (reference wire parity)."""
        import jax.numpy as jnp

        from veles_tpu.dummy import DummyWorkflow
        from veles_tpu.memory import Array
        from veles_tpu.nn.attention import GDSelfAttention
        from veles_tpu.nn.gd import GradientDescent

        wf = DummyWorkflow()
        attn = GDSelfAttention(wf)
        for attr, shape in (("weights", (4, 12)), ("bias", (12,)),
                            ("out_weights", (4, 4)), ("out_bias", (4,))):
            setattr(attn, attr, Array(numpy.ones(shape, numpy.float32)))
        job = attn.generate_data_for_slave()
        assert {"weights", "bias", "out_weights", "out_bias",
                "lr", "lr_bias"} <= set(job)
        momentum = GradientDescent(wf)
        momentum.weights = Array(numpy.ones((3, 2), numpy.float32))
        momentum.bias = Array(numpy.ones(2, numpy.float32))
        assert momentum._solver_state_attrs() == []
        adam = GradientDescent(wf, solver="adam")
        adam.weights = Array(numpy.ones((3, 2), numpy.float32))
        adam.bias = Array(numpy.ones(2, numpy.float32))
        adam.weights.to_device()
        adam.bias.to_device()
        adam.initialize()
        adam._velocity_w.data = jnp.full((3, 2), 0.5)
        adam._second_w.data = jnp.full((3, 2), 0.25)
        adam._step.data = jnp.asarray(7.0)
        update = adam.generate_data_for_master()
        assert {"_velocity_w", "_velocity_b", "_second_w", "_second_b",
                "_step"} <= set(update)
        # master applies the moments (overwrite, regardless of merge)
        master = GradientDescent(wf, solver="adam")
        master.weights = Array(numpy.zeros((3, 2), numpy.float32))
        master.bias = Array(numpy.zeros(2, numpy.float32))
        master.weights.to_device()
        master.bias.to_device()
        master.initialize()
        master.apply_data_from_slave(update)
        numpy.testing.assert_allclose(
            numpy.asarray(master._second_w.data), 0.25)
        assert float(master._step.data) == 7.0
        # and the next job ships them back down (respawned slave
        # resumes its estimates)
        job = master.generate_data_for_slave()
        assert "_second_w" in job and "_step" in job
        slave = GradientDescent(wf, solver="adam")
        slave.weights = Array(numpy.zeros((3, 2), numpy.float32))
        slave.bias = Array(numpy.zeros(2, numpy.float32))
        slave.weights.to_device()
        slave.bias.to_device()
        slave.initialize()
        slave.apply_data_from_master(job)
        numpy.testing.assert_allclose(
            numpy.asarray(slave._velocity_w.data), 0.5)
        assert float(slave._step.data) == 7.0

    def test_average_merge_mode(self, monkeypatch):
        from veles_tpu.core.config import root
        from veles_tpu.dummy import DummyWorkflow
        from veles_tpu.nn.gd import GradientDescent

        monkeypatch.setattr(root.common.fleet, "merge", "average",
                            raising=False)
        from veles_tpu.memory import Array
        gd = GradientDescent(DummyWorkflow())
        gd.weights = Array(numpy.full((2, 2), 4.0, numpy.float32))
        gd.bias = Array(numpy.full(2, 4.0, numpy.float32))
        gd.weights.to_device()
        gd.bias.to_device()
        gd.apply_data_from_slave(
            {"weights": numpy.zeros((2, 2), numpy.float32),
             "bias": numpy.zeros(2, numpy.float32)})
        numpy.testing.assert_allclose(numpy.asarray(gd.weights.mem), 2.0)
        numpy.testing.assert_allclose(numpy.asarray(gd.bias.mem), 2.0)
        # unknown mode rejected
        monkeypatch.setattr(root.common.fleet, "merge", "bogus",
                            raising=False)
        with pytest.raises(ValueError):
            gd.apply_data_from_slave(
                {"weights": numpy.zeros((2, 2), numpy.float32),
                 "bias": numpy.zeros(2, numpy.float32)})

    def test_async_slave_mode(self):
        kw = _kw(max_epochs=2)
        master, wf_m, thread = _run_master(kw)
        slave = _run_slave(master.agent.port, kw, async_slave=True)
        slave.run()
        thread.join(60)
        assert not thread.is_alive()
        assert wf_m.decision.best_n_err[VALID] is not None
        master.stop()
        slave.stop()

    def test_drop_slave_requeues_minibatches(self):
        """A disconnected slave's pending work must be requeued and the
        epoch still complete exactly (reference drop_slave semantics)."""
        kw = _kw(max_epochs=1)
        master, wf_m, thread = _run_master(kw)
        loader = wf_m.loader
        # simulate: serve a job to a fake slave, then drop it
        class FakeSlave:
            id = "fake-1"
        job = loader.generate_data_for_slave(FakeSlave())
        assert loader.pending_minibatches_["fake-1"]
        loader.drop_slave(FakeSlave())
        assert len(loader.failed_minibatches) == 1
        # a real slave now runs everything, including the requeued batch
        slave = _run_slave(master.agent.port, kw)
        slave.run()
        thread.join(60)
        assert not thread.is_alive()
        # requeued minibatch was re-served: total samples == 1 full epoch
        # + the duplicated minibatch
        assert wf_m.decision.best_n_err[VALID] is not None
        master.stop()
        slave.stop()


class TestRespawn:
    def test_manager_backoff_and_budget(self):
        from veles_tpu.fleet.respawn import RespawnManager

        spawned = []
        mgr = RespawnManager(
            spawner=lambda host, cmd, cwd=None, env=None:
            spawned.append((host, cmd, cwd, env)),
            max_attempts=2, base_delay=0.01)
        recipe = {"executable": "/usr/bin/python3",
                  "argv": ["wf.py", "-m", "h:1"],
                  "cwd": "/work", "pythonpath": "/lib"}
        assert mgr.schedule("10.0.0.5", recipe, key="mid-1")
        assert mgr.schedule("10.0.0.5", recipe, key="mid-1")
        # budget exhausted
        assert not mgr.schedule("10.0.0.5", recipe, key="mid-1")
        import time as _t
        deadline = _t.time() + 5
        while len(spawned) < 2 and _t.time() < deadline:
            _t.sleep(0.01)
        assert len(spawned) == 2
        host, cmd, cwd, env = spawned[0]
        assert host == "10.0.0.5" and cwd == "/work"
        assert env == {"PYTHONPATH": "/lib"}
        assert "-b" in cmd and "wf.py" in cmd  # daemonized relaunch
        # a self-reconnect resets the budget
        mgr.notify_reconnected("mid-1")
        assert mgr.schedule("10.0.0.5", recipe, key="mid-1")
        mgr.stop()

    def test_incomplete_recipe_rejected(self):
        from veles_tpu.fleet.respawn import RespawnManager

        mgr = RespawnManager(spawner=lambda *a, **k: None)
        assert not mgr.schedule("h", {})
        assert not mgr.schedule("h", {"executable": "python"})

    def test_server_respawns_dropped_slave(self):
        """Loopback: a dying slave with a recipe triggers the master's
        respawn schedule (reference server.py:637-655 semantics)."""
        spawned = []
        kw = _kw(max_epochs=2)
        _seed()
        master = Launcher(listen_address="127.0.0.1:0", respawn=True)
        wf_m = MLPWorkflow(master, name="fleet-t", **kw)
        master.initialize()
        master.agent.respawn_manager.spawner = \
            lambda host, cmd, cwd=None, env=None: spawned.append(
                (host, cmd))
        master.agent.respawn_manager.base_delay = 0.01
        mthread = threading.Thread(target=master.run, daemon=True)
        mthread.start()
        slave = _run_slave(master.agent.port, kw, respawn=True)
        sthread = threading.Thread(target=slave.run, daemon=True)
        sthread.start()
        import time as _t
        deadline = _t.time() + 10
        while not master.agent.slaves and _t.time() < deadline:
            _t.sleep(0.05)
        assert master.agent.slaves, "slave never connected"
        # abrupt death: close the transport with no 'bye' (the in-process
        # stand-in for the fault injection's os._exit)
        slave.agent.stop()
        deadline = _t.time() + 10
        while not spawned and _t.time() < deadline:
            _t.sleep(0.05)
        master.stop()
        slave.stop()
        assert spawned, "master never scheduled a respawn"
        host, cmd = spawned[0]
        assert host in ("127.0.0.1", "::1")
        assert "-b" in cmd


class TestChecksum:
    def test_checksum_mismatch_rejected(self):
        import types

        kw = _kw(max_epochs=1)
        master, wf_m, thread = _run_master(kw)
        slave = _run_slave(master.agent.port, kw)
        # a class-level checksum patch would hit the master too (same class
        # in-process), so swap the CLIENT's workflow for a bogus-checksum
        # stand-in instead
        slave.agent.workflow = types.SimpleNamespace(checksum="bogus")
        try:
            slave.run()
            assert slave.agent.jobs_done == 0
        finally:
            master.stop()
            slave.stop()
            thread.join(1)


class TestSafeCodec:
    """fleet/safecodec.py + the codec="safe" wire mode: a leaked secret
    must not be remote code execution."""

    @pytest.fixture
    def safe_wire(self):
        from veles_tpu.core.config import root
        saved = root.common.fleet.get("codec", "pickle")
        root.common.fleet.codec = "safe"
        yield
        root.common.fleet.codec = saved

    def test_roundtrip_structures(self):
        from veles_tpu.fleet import safecodec
        import jax.numpy as jnp

        msg = {
            "type": "job",
            "n": 7, "f": 1.5, "flag": True, "none": None,
            "name": "unit", "raw": b"\x00\xffbytes",
            "list": [1, [2.5, "x"], {"k": (1, 2)}],
            "tuple": (3, "y"),
            5: "int-key", (1, "t"): "tuple-key",
            "arr": numpy.arange(12, dtype=numpy.float32).reshape(3, 4),
            "i64": numpy.arange(3, dtype=numpy.int64),
            "jax": jnp.ones((2, 2), jnp.bfloat16),
            "scalar": numpy.float32(2.25),
        }
        out = safecodec.loads(safecodec.dumps(msg))
        assert out["type"] == "job" and out["n"] == 7
        assert out["f"] == 1.5 and out["flag"] is True
        assert out["none"] is None and out["raw"] == b"\x00\xffbytes"
        assert out["list"] == [1, [2.5, "x"], {"k": (1, 2)}]
        assert out["tuple"] == (3, "y")
        assert out[5] == "int-key" and out[(1, "t")] == "tuple-key"
        numpy.testing.assert_array_equal(out["arr"], msg["arr"])
        assert out["arr"].dtype == numpy.float32
        assert out["i64"].dtype == numpy.int64
        assert out["jax"].dtype == numpy.dtype("bfloat16")
        numpy.testing.assert_array_equal(
            out["jax"].astype(numpy.float32), numpy.ones((2, 2)))
        assert out["scalar"] == numpy.float32(2.25)
        assert type(out["scalar"]) is numpy.float32  # not a 0-d array

    def test_numpy_keys_coerced_at_encode(self):
        """Numpy-scalar dict keys (bare or inside tuple keys) must
        round-trip as working lookups, not explode at the receiver."""
        from veles_tpu.fleet import safecodec

        msg = {numpy.int64(3): "a", (numpy.int32(1), "t"): "b"}
        out = safecodec.loads(safecodec.dumps(msg))
        assert out[3] == "a" and out[(1, "t")] == "b"
        with pytest.raises(safecodec.UnsupportedType, match="dict key"):
            safecodec.dumps({frozenset((1,)): "x"})

    def test_malformed_safe_frame_is_protocol_error(self, safe_wire):
        """A malformed-but-authenticated safe frame must surface as
        ProtocolError (peer dropped), never a raw KeyError/ValueError
        that would kill the fleet session loop."""
        import gzip as gzip_lib
        import json
        import struct as struct_lib

        from veles_tpu.fleet.protocol import (
            ProtocolError, _mac, read_frame)

        deep = b"[" * 50000 + b"1" + b"]" * 50000  # RecursionError bait
        for header in ({"x": 1},                       # missing 't'
                       {"t": "a", "d": "<f4",
                        "s": [5, 5], "o": 0, "n": 4},  # bad reshape
                       {"t": "zz"},                    # unknown node
                       deep):
            head = (header if isinstance(header, bytes)
                    else json.dumps(header).encode())
            payload = struct_lib.pack(">I", len(head)) + head + b"\0" * 4
            if len(payload) >= 64 * 1024:
                payload = gzip_lib.compress(payload)
            frame = (struct_lib.pack(">IB", len(payload), 2)
                     + _mac(KEY, 2, payload) + payload)
            with pytest.raises(ProtocolError, match="bad safe frame"):
                asyncio.run(read_frame(FakeReader(frame), KEY))

    def test_unsupported_type_fails_at_encode(self):
        from veles_tpu.fleet import safecodec

        class Payload:
            pass

        with pytest.raises(safecodec.UnsupportedType,
                           match="Payload"):
            safecodec.dumps({"job": Payload()})
        with pytest.raises(safecodec.UnsupportedType):
            safecodec.dumps(numpy.array([object()], dtype=object))

    def test_safe_receiver_rejects_pickle_frames(self, safe_wire):
        """THE security property: a safe-configured host never reaches
        pickle.loads, even for a correctly authenticated frame."""
        from veles_tpu.core.config import root
        from veles_tpu.fleet.protocol import ProtocolError, read_frame

        root.common.fleet.codec = "pickle"
        pickle_frame = encode_frame({"type": "hello"}, KEY)
        root.common.fleet.codec = "safe"
        with pytest.raises(ProtocolError, match="safe fleet codec"):
            asyncio.run(read_frame(FakeReader(pickle_frame), KEY))

    def test_safe_frame_roundtrip_and_compression(self, safe_wire):
        from veles_tpu.fleet.protocol import read_frame

        msg = {"type": "job",
               "job": [numpy.zeros(1024 * 1024, numpy.float32),
                       {"lr": 0.5}]}
        frame = encode_frame(msg, KEY)
        assert len(frame) < 1024 * 1024  # gzip applies to safe frames too
        out = asyncio.run(read_frame(FakeReader(frame), KEY))
        numpy.testing.assert_array_equal(out["job"][0], msg["job"][0])
        assert out["job"][1] == {"lr": 0.5}

    def test_fleet_trains_on_safe_codec(self, safe_wire):
        """The PRODUCT path: master + slave converge identically to the
        standalone run with zero pickle on the wire."""
        kw = _kw()
        _seed()
        lau = Launcher()
        wf_sa = MLPWorkflow(lau, name="fleet-t", **kw)
        lau.initialize()
        lau.run()
        expected = wf_sa.decision.best_n_err[VALID]

        master, wf_m, thread = _run_master(kw)
        slave = _run_slave(master.agent.port, kw)
        slave.run()
        thread.join(60)
        assert not thread.is_alive(), "master did not finish"
        assert wf_m.decision.best_n_err[VALID] == expected
        master.stop()
        slave.stop()


def test_lr_decay_reaches_slaves():
    """Master-side plateau annealing must propagate: the decayed rates
    ride the job payloads, so the slave that executes the GD ticks
    anneals too."""
    kw = _kw(max_epochs=6, minibatch=300)
    kw["learning_rate"] = 1e-7  # guaranteed plateau after epoch 1
    master, wf_m, thread = _run_master(kw)
    wf_m.decision.lr_decay = 0.5
    wf_m.decision.lr_decay_patience = 2
    slave = _run_slave(master.agent.port, kw)
    wf_s = slave.workflow
    slave.run()
    thread.join(120)
    assert not thread.is_alive(), "master did not finish"
    assert wf_m.gds[0].learning_rate < 1e-7  # master decayed
    assert wf_s.gds[0].learning_rate < 1e-7  # ...and the slave followed
    master.stop()
    slave.stop()
