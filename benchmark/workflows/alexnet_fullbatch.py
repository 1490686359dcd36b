"""The AlexNet full-batch training job the benchmark times, as a
workflow file for ``python -m veles_tpu`` (``Main().run([this file,
"-", "root.benchmark.<key>=<value>", ...])``).

It is ``AlexNetWorkflow`` as ``veles_tpu/models/alexnet.py`` builds it,
on pixels the benchmark makes from ``--seed``. The one addition is the
benchmark's clock around the fused tick and the decision
(``benchmark/harness/train_fullbatch.Session``): it reads times and
the first epoch's outputs and ends the run when the window is up; it
changes nothing the units compute.
"""

from veles_tpu.core.config import root
from veles_tpu.models.alexnet import AlexNetWorkflow

from benchmark.harness import train_fullbatch

root.benchmark.update({"config": "", "traffic": "", "seed": 0,
                       "seconds": 10.0, "trace": 0, "started": 0.0,
                       "rehearse": 0, "control": "", "workload": ""})


class TimedAlexNet(AlexNetWorkflow):
    """``AlexNetWorkflow`` that lets the benchmark's session watch its
    run (``self.bench``, set by ``Session.install``)."""

    def run(self):
        self.bench.attach(self)
        return super().run()


def run(load, main):
    session = train_fullbatch.Session({
        key: root.benchmark.get(key)
        for key in ("config", "traffic", "seed", "seconds", "trace",
                    "started", "rehearse", "control", "workload")})
    workflow, _ = load(TimedAlexNet, **session.workflow_kwargs())
    session.install(workflow)
    main()
