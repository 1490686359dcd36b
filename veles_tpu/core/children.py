"""Children that need the device: one at a time, stderr kept.

The meta-workflows (ensembles, genetics) evaluate by running
``python -m veles_tpu`` children. Each child trains on the
accelerator, and a chip belongs to ONE process at a time — so children
run strictly one after another, and a parent that has itself
initialised the TPU backend holds the chip and must not spawn one (the
child would fail to open the device or hang on it).
"""

import os
import subprocess

from veles_tpu.core.config import root


def run_device_child(cmd, tag):
    """Run ``cmd`` to completion and return ``(returncode,
    stderr_path)``. The child's stderr is kept in
    ``<root.common.dirs.run>/<tag>-<parent pid>.stderr`` — a failed
    evaluation is diagnosable instead of a bare return code. Raises
    when this process already holds the TPU."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "this process has initialised the TPU backend and holds "
                "the chip; a child that needs it would fail or hang. "
                "Spawn %s children from a process that has not touched "
                "JAX" % tag)
    run_dir = root.common.dirs.get("run", ".")
    os.makedirs(run_dir, exist_ok=True)
    stderr_path = os.path.join(run_dir,
                               "%s-%d.stderr" % (tag, os.getpid()))
    with open(stderr_path, "wb") as err:
        proc = subprocess.run(cmd, stdout=subprocess.DEVNULL, stderr=err)
    return proc.returncode, stderr_path
