"""One process of the two-process pod-parity test (NOT a pytest module).

Spawned by ``tests/test_pod_mode.py``: joins a 2-process jax.distributed
pod (1 CPU device each), runs the PRODUCT path — ``Launcher`` +
``MLPWorkflow`` with the mesh coming from ``root.common.mesh.axes`` —
and (process 0) dumps the final metrics + weights so the parent can
assert bit-for-bit parity with a single-process 2-device run.

Usage: python tests/pod_child.py PROC_ID NPROCS COORD_PORT OUT_JSON
"""

import json
import os
import sys
import tempfile

proc_id, nprocs, port, out_path = (int(sys.argv[1]), int(sys.argv[2]),
                                   sys.argv[3], sys.argv[4])

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if "xla_force_host_platform_device_count" not in f]
    + ["--xla_force_host_platform_device_count=1"])
os.environ.setdefault("VELES_TPU_HOME",
                      tempfile.mkdtemp(prefix="veles_pod_child_"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
from veles_tpu.parallel.mesh import initialize_distributed  # noqa: E402

initialize_distributed("127.0.0.1:" + port, nprocs, proc_id)

import numpy  # noqa: E402

from veles_tpu.core import prng  # noqa: E402
from veles_tpu.core.config import root  # noqa: E402
from veles_tpu.launcher import Launcher  # noqa: E402
from veles_tpu.loader.base import VALID  # noqa: E402
from veles_tpu.models.mlp import MLPWorkflow  # noqa: E402

root.common.disable.plotting = True
root.common.disable.snapshotting = True
root.common.mesh.axes.data = 2  # the product pod-mode switch

prng.get("default").seed(4321)
prng.get("loader").seed(8765)

from dataset_fixtures import digits_dataset  # noqa: E402

X, y = digits_dataset()

launcher = Launcher()
wf = MLPWorkflow(
    launcher, layers=(32, 10),
    loader_kwargs=dict(data=X, labels=y,
                       class_lengths=[0, 297, 1500], minibatch_size=100,
                       normalization_type="linear"),
    learning_rate=0.1, max_epochs=3, name="pod-child")
launcher.initialize()
assert wf.fused_tick is not None and wf.fused_tick.mesh is not None, \
    "pod mode did not engage from config"
launcher.run()

if proc_id == 0:
    payload = {
        "best_n_err": int(wf.decision.best_n_err[VALID]),
        "epochs": int(wf.decision._epochs_done),
        "weights": [numpy.asarray(f.weights.data).tolist()
                    for f in wf.forwards],
    }
    with open(out_path, "w") as fout:
        json.dump(payload, fout)
jax.distributed.shutdown()
