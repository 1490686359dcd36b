"""kernel.conv_roofline.train: Over the convolution ops of the train sweep (forward, input- and
weight-gradient, found by the three tensors of a conv layer in the
op's text): the sum of max(operations / peak FLOP/s, bytes / peak
bytes/s) over the sum of their device durations. Operations and
bytes are ``benchmark/ops``'s, the same whatever implements the
convolution."""

LAYER = 'Kernels (ops/gemm.conv2d, XLA convolutions)'
MOVES = 'train_images_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import trace

    reduced, peaks = ctx["reduced"], ctx["peaks"]
    sweeps = trace.modules_named(reduced["trace"], "train_sweep",
                                 reduced["window"])
    batch = ctx["counters"]["minibatch"]
    least = spent = 0.0
    for text, _, duration in trace.ops_inside(reduced["trace"], sweeps):
        shapes = trace.parse_op(text)["shapes"]
        role = ctx["ops"].conv_role(ctx["config"], batch, shapes)
        if role is None:
            continue
        ops, nbytes = ctx["ops"].conv_ops_and_bytes(
            ctx["config"], batch, role[0], shapes)
        least += max(ops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
        spent += duration / 1e9
    return 100.0 * least / spent if spent else None
