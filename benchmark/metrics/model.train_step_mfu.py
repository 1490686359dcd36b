"""model.train_step_mfu: Operations the forward and backward passes need per image
(``benchmark/ops``: from the layer shapes, conv1 without an input
gradient) x train images a second of the traced window, over the
chip's bf16 peak. The window's length and the sweeps in it are the
trace's."""

LAYER = 'Model step (parallel/fused.py tick)'
MOVES = 'train_images_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import trace

    reduced = ctx["reduced"]
    sweeps = trace.modules_named(reduced["trace"], "train_sweep",
                                 reduced["window"])
    if not sweeps or not reduced["window_s"]:
        return None
    images = (len(sweeps) * ctx["counters"]["steps_per_train_sweep"]
              * ctx["counters"]["minibatch"])
    ops = ctx["ops"].train_ops_per_image(ctx["config"])
    return (100.0 * ops * images / reduced["window_s"]
            / ctx["peaks"]["bf16_flops_per_s"])
