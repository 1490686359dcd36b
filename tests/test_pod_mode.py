"""Pod mode as a PRODUCT mode.

Three tiers:

- the mesh config (``root.common.mesh.axes`` / ``--mesh``) actually
  reaches a running ``StandardWorkflow`` through the real ``Launcher``;
- the CLI flag trains sharded end to end (subprocess over a 4-device
  virtual CPU platform);
- a 2-process ``jax.distributed`` pod (1 device each) matches the
  single-process 2-device run bit-for-bit — the multi-host path.
"""

import json
import os
import socket
import subprocess
import sys

import numpy
import pytest

from veles_tpu.core import prng
from veles_tpu.core.config import root
from veles_tpu.launcher import Launcher
from veles_tpu.loader.base import VALID
from veles_tpu.models.mlp import MLPWorkflow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _digits():
    from dataset_fixtures import digits_dataset
    return digits_dataset()


def _build(mesh=None, minibatch_size=96):
    # default 96: divisible by the 8-device data axis AND the 4-device
    # reference mesh; the 2-process parity test uses 100 to match
    # tests/pod_child.py
    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    X, y = _digits()
    launcher = Launcher()
    wf = MLPWorkflow(
        launcher, layers=(32, 10),
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 297, 1500],
                           minibatch_size=minibatch_size,
                           normalization_type="linear"),
        learning_rate=0.1, max_epochs=3, mesh=mesh, name="pod-product")
    return launcher, wf


def test_mesh_config_reaches_product_path():
    """root.common.mesh.axes alone must put the workflow into sharded
    pod mode through the real Launcher (no mesh= kwarg anywhere), and
    the numbers must match the explicitly-meshed run."""
    import jax
    from veles_tpu.parallel.mesh import build_mesh

    launcher_ref, ref = _build(
        mesh=build_mesh(devices=jax.devices()[:4], data=4))
    launcher_ref.initialize()
    launcher_ref.run()

    root.common.mesh.axes.data = -1  # absorb all 8 virtual devices
    try:
        launcher, wf = _build()
        launcher.initialize()
        assert wf.fused_tick is not None
        assert wf.fused_tick.mesh is not None, \
            "configured mesh did not reach the workflow"
        assert wf.fused_tick.mesh.shape["data"] == len(jax.devices())
        launcher.run()
    finally:
        root.common.mesh.axes.data = 1
    # dp8 vs dp4: psum-merged grads equal full-batch grads up to float
    # reassociation (different reduction trees), compounding over the
    # run — metrics stay exact, weights stay close
    assert wf.decision.best_n_err[VALID] == ref.decision.best_n_err[VALID]
    for fa, fb in zip(wf.forwards, ref.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fa.weights.data), numpy.asarray(fb.weights.data),
            atol=2e-2)


def test_configured_mesh_that_cannot_be_honoured_raises():
    """A mesh the run cannot honour is an error naming the knob — never
    a warning and a one-device run at 1/Nth speed (as fused=True
    already refused to degrade)."""
    from veles_tpu.core.workflow import Workflow

    root.common.mesh.axes.data = -1
    try:
        # minibatch 100 does not divide by the 8-device data axis
        launcher, wf = _build(minibatch_size=100)
        with pytest.raises(ValueError, match="--mesh"):
            launcher.initialize()
        # a workflow with no mesh slot at all
        launcher = Launcher()
        Workflow(launcher, name="meshless")
        with pytest.raises(ValueError, match="no mesh support"):
            launcher.initialize()
    finally:
        root.common.mesh.axes.data = 1


@pytest.mark.slow
def test_cli_mesh_flag_trains_sharded(tmp_path):
    """`python -m veles_tpu samples/digits_mlp.py --mesh data=4` — the
    done-criterion for CLI reachability."""
    result_file = str(tmp_path / "results.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               VELES_TPU_HOME=str(tmp_path / "home"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "veles_tpu", "samples/digits_mlp.py",
         "samples/digits_config.py", "root.digits.max_epochs=2",
         "--mesh", "data=4", "--seed", "7", "--result-file", result_file],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "pod mode: mesh" in proc.stderr + proc.stdout
    results = json.load(open(result_file))
    assert results["epochs"] == 2
    assert results["best_validation_errors"] < 297


@pytest.mark.slow
def test_two_process_pod_matches_single_process(tmp_path):
    """Two jax.distributed processes (1 device each) running the product
    path must reproduce the single-process 2-device run exactly."""
    import jax
    from veles_tpu.parallel.mesh import build_mesh

    launcher, ref = _build(mesh=build_mesh(devices=jax.devices()[:2],
                                           data=2), minibatch_size=100)
    launcher.initialize()
    launcher.run()

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
    out = str(tmp_path / "pod0.json")
    env = dict(os.environ,
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tests", "pod_child.py"),
         str(pid), "2", str(port), out],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for pid in range(2)]
    fail = []
    try:
        for pid, proc in enumerate(procs):
            _, err = proc.communicate(timeout=600)
            if proc.returncode:
                fail.append("child %d rc=%d:\n%s"
                            % (pid, proc.returncode, err[-2000:]))
    finally:
        for proc in procs:
            if proc.poll() is None:
                # a crashed sibling leaves the other parked in the
                # jax.distributed barrier — never leak it past the test
                proc.kill()
    assert not fail, "\n".join(fail)
    got = json.load(open(out))
    assert got["epochs"] == ref.decision._epochs_done
    assert got["best_n_err"] == ref.decision.best_n_err[VALID]
    for child_w, fwd in zip(got["weights"], ref.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(child_w, numpy.float32),
            numpy.asarray(fwd.weights.data), atol=1e-6)
