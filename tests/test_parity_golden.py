"""Golden accuracy-parity harness.

Offline it always runs: the three reference topology families on the
real 8x8 UCI digits with ABSOLUTE error bounds (3.0% / 0.7% / 0.7% —
the convnets at sub-anchor error via the shift1 augmentation), writing
PARITY.json. On a host with real MNIST idx files, set
``VELES_TPU_MNIST_DIR`` and the full reference-anchor run
(≤2.2% / ≤1.0% / ≤0.9%) executes instead.
"""

import json
import os

import pytest

from veles_tpu import parity


@pytest.mark.slow
def test_parity_synthetic_mlp(tmp_path, monkeypatch):
    """The MLP family must beat its absolute bound on digits — the
    quick anchor (the conv families run in the full harness below).
    Synthetic mode is pinned: without the delenv, a host with
    VELES_TPU_MNIST_DIR exported would silently train the digits
    topologies on real MNIST (run_parity falls back to the env var)."""
    monkeypatch.delenv("VELES_TPU_MNIST_DIR", raising=False)
    out = str(tmp_path / "PARITY.json")
    verdict = parity.run_parity(
        mnist_dir=None, out=out,
        topologies=parity.DIGITS_TOPOLOGIES[:1])
    assert verdict["mode"] == "real-digits-8x8"
    written = json.load(open(out))
    assert written["results"][0]["name"] == "digits784"
    assert written["results"][0]["pass"], written
    assert written["pass"]


@pytest.mark.slow
def test_parity_full_harness(tmp_path):
    """The complete harness: all three topology families produce a
    verdict artifact; real MNIST when VELES_TPU_MNIST_DIR is set,
    the digits analogue otherwise. Every family must pass its bound."""
    mnist_dir = os.environ.get("VELES_TPU_MNIST_DIR") or None
    out = str(tmp_path / "PARITY.json")
    verdict = parity.run_parity(mnist_dir=mnist_dir, out=out)
    assert os.path.exists(out)
    assert len(verdict["results"]) == 3
    for entry in verdict["results"]:
        assert entry["pass"], entry
    assert verdict["pass"]
