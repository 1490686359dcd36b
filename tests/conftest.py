"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (see README / driver
contract). Must set env before jax initializes."""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# the suite's compile cache: a FIXED path outside the checkout, placed
# through the environment variable veles_tpu.core.config honours — so
# tier-1 neither writes into the tree the chip tool copies nor loses
# its hits to a directory that moves every run
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "veles_tpu_tier1_jax_cache"))
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# keep ALL framework cache/state artifacts out of the user's home:
# config.py derives every dir from VELES_TPU_HOME, which must be set
# before veles_tpu imports
_tmp = tempfile.mkdtemp(prefix="veles_tpu_test_")
os.environ["VELES_TPU_HOME"] = _tmp

from veles_tpu.core.config import root  # noqa: E402

root.common.disable.plotting = True
# the metric flight recorder (observe/history.py) is default-on at a
# 1 s cadence wherever /metrics mounts; each sample runs EVERY
# registry collector, including the per-device live-buffer memory
# walk, for the remainder of the session — at test scale that bleeds
# tier-1's timeout margin. Keep the default-on wiring exercised but
# sample lazily; tests that need a fast cadence build their own
# MetricHistory (tests/test_history.py does).
root.common.observe.history = "interval_s=30"
