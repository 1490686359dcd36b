"""engine.step_ms.train: Device time of the train-sweep program (``XLA Modules`` events named
``*train_sweep*``) over the minibatch steps it scans."""

LAYER = 'Workflow engine (models/standard.py, parallel/fused.py, nn/decision.py)'
MOVES = 'train_images_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import trace

    reduced = ctx["reduced"]
    sweeps = trace.modules_named(reduced["trace"], "train_sweep",
                                 reduced["window"])
    if not sweeps:
        return None
    steps = len(sweeps) * ctx["counters"]["steps_per_train_sweep"]
    return sum(m[2] for m in sweeps) / 1e6 / steps
