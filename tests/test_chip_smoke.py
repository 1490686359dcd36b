"""chip_smoke.py's logic, on the CPU, without relaxing its TPU-only entry.

The script refuses every platform but the TPU, so its phases are
exercised here as FUNCTIONS at toy widths (the same code the chip runs
at full width), and the entry itself is checked to fail on the CPU
naming the platform.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY_SERVE = dict(blocks=2, embed=32, heads=4, vocab=64, slots=2,
                 prompt=16, n_tokens=8, chunk=4, n_requests=4,
                 clients=2, page_size=8)
TOY_MNIST = ("root.synthetic.n_train=400", "root.synthetic.n_valid=100",
             "root.synthetic.minibatch_size=20")


@pytest.fixture
def serve_config():
    """serve_phase writes the --serve-* landing spots; put them back."""
    from veles_tpu.core.config import root
    keys = ("paged", "page_size", "mesh")
    saved = {key: root.common.serve.get(key, None) for key in keys}
    yield
    for key, value in saved.items():
        setattr(root.common.serve, key, value)


def test_entry_refuses_the_cpu(tmp_path):
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu: non-zero exit,
    the platform named, no result line on stdout."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=str(tmp_path),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform is 'cpu', not 'tpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_device_facts_name_the_device():
    facts = chip_smoke.device_facts()
    assert facts["platform"] == "cpu" and facts["device_count"] == 8
    assert facts["jax"] and facts["jaxlib"]
    assert facts["compile_cache"] == os.environ[
        "JAX_COMPILATION_CACHE_DIR"]
    assert facts["site_config"] is False


def test_train_phase_through_the_cli():
    out = chip_smoke.train_phase("mnist784", overrides=TOY_MNIST)
    assert out["ran"]["epochs"] == 3
    assert out["train_loss"][-1] < out["train_loss"][0]
    assert out["smoke_timing_first_dispatch_s"] > 0


def test_train_phase_alexnet_topology():
    out = chip_smoke.train_phase("alexnet", overrides=(
        "root.synthetic.scale=0.05",
        "root.synthetic.sample_shape=(67,67,3)",
        "root.synthetic.n_train=64", "root.synthetic.n_valid=32",
        "root.synthetic.minibatch_size=16"))
    assert out["ran"]["epochs"] == 2


@pytest.fixture
def mesh_config():
    """--mesh lands in root.common.mesh.axes for the process; the chip
    runs each phase in its own, a test must put the axis back."""
    from veles_tpu.core.config import root
    yield
    root.common.mesh.axes.data = 1


@pytest.mark.mesh
def test_train_phase_mesh_lays_state_over_the_devices(mesh_config):
    # the chip phase says data=4; the virtual CPU platform has 8
    out = chip_smoke.train_phase("mnist784", mesh="data=8", overrides=(
        "root.synthetic.n_train=400", "root.synthetic.n_valid=120",
        "root.synthetic.minibatch_size=40"))
    assert "over 8 devices" in out["pod_mode"]
    assert out["devices_holding"]["params"] == 8


def test_train_phase_fails_without_two_epochs_to_compare():
    """The checks are live: a run too short to show a falling loss
    fails the phase instead of passing it."""
    with pytest.raises(AssertionError, match="epochs recorded"):
        chip_smoke.train_phase("mnist784", overrides=TOY_MNIST + (
            "root.synthetic.max_epochs=1",))


def test_serve_phase_dense_and_int8(serve_config):
    out = chip_smoke.serve_phase(**TOY_SERVE)
    assert out["attend"] == "dense slab"
    assert out["ran"]["requests"] == 8
    out = chip_smoke.serve_phase(quantize="int8", **TOY_SERVE)
    assert out["quantize"] == "int8"


@pytest.mark.paged
def test_serve_phase_paged_books_hit_and_tail(serve_config):
    out = chip_smoke.serve_phase(paged=True, **TOY_SERVE)
    assert out["attend"] == "gather"   # the CPU's formulation, read
    counts = out["dispatch_counts"]    # from the decoder
    assert counts["admit_hit"] >= 1 and counts["admit_tail"] >= 1


@pytest.mark.mesh
def test_serve_phase_mesh_shards_the_kv_state(serve_config):
    out = chip_smoke.serve_phase(mesh="model=8",
                                 **dict(TOY_SERVE, heads=8))
    assert out["devices_holding"] == {"kv_state": 8}


@pytest.mark.paged_kernel
def test_paged_kernel_agreement_interpret_mode():
    errs = chip_smoke.paged_kernel_agreement(
        heads=4, head_dims=(8,), page_size=8, slots=3, pages_per_slot=3)
    assert set(errs) == {"bfloat16_d8", "float32_d8", "int8_d8"}


def test_parent_fails_on_a_missing_phase_line(tmp_path, monkeypatch):
    """A child that exits 0 without its line is a failure, and so is a
    non-zero exit — the parent never books a phase it did not see."""
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setitem(chip_smoke.CHILDREN, "x", (60, (("x", dict),)))
    script = tmp_path / "child.py"
    monkeypatch.setattr(chip_smoke, "__file__", str(script))
    script.write_text("print('no json here')\n")
    with pytest.raises(RuntimeError, match="no passing line"):
        chip_smoke.run_child("x", 60, lambda text: None)
    script.write_text("import sys; sys.exit(3)\n")
    with pytest.raises(RuntimeError, match="exited 3"):
        chip_smoke.run_child("x", 60, lambda text: None)
    script.write_text("import time; time.sleep(60)\n")
    with pytest.raises(RuntimeError, match="timed out"):
        chip_smoke.run_child("x", 1, lambda text: None)


def _fake_rows(name):
    return [{"phase": phase, "ok": True, "platform": "tpu",
             "device_kind": "TPU v5 lite", "device_count": 1,
             "jax": "j", "jaxlib": "jl", "libtpu": "lt",
             "compile_cache": "/c"}
            for phase, _ in chip_smoke.CHILDREN[name][1]]


@pytest.mark.parametrize("failing", [None, "serve_paged"])
def test_last_stdout_line_is_the_verdict_and_nothing_else(
        tmp_path, monkeypatch, capsys, failing):
    """The driver reads the LAST line of stdout: one JSON object with
    exactly "ok" and "device" {platform, kind, count}. Everything else
    the run has to say (versions, phases, claim) rides the summary line
    before it; a failed phase turns the verdict false and the exit 1."""
    import json

    def run_child(name, timeout, log):
        if name == failing:
            raise RuntimeError("phase child %s exited 1" % name)
        return _fake_rows(name)

    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke, "run_child", run_child)
    assert chip_smoke.main() == (1 if failing else 0)
    lines = capsys.readouterr().out.strip().splitlines()
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": failing is None, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert isinstance(verdict["device"]["count"], int)
    summary = json.loads(lines[-2])
    assert summary["claim"] is None and "ok" not in summary
    assert ("serve_paged" in summary["phases"]) == (failing is None)
    assert "multichip: skipped, 1 device(s)" in lines
