"""kernel.decode_step_roofline.serve: max(operations / peak FLOP/s, bytes / peak bytes/s) of a decode
step over the mean device time of a step in the trace. Operations and
bytes are ``benchmark/ops``'s for each chunk the decoder dispatched
inside the traced window, at the lengths its occupied slots held by
the decoder's own books (``readings.chunks_in``): the weights once,
and the K/V of the positions those slots have cached; the mean over
the chunks. At these sizes the bytes bound holds."""

LAYER = 'Kernels (decode-step program: matmul_any, slab attend)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import readings, trace

    reduced, peaks = ctx["reduced"], ctx["peaks"]
    programs = trace.modules_named(reduced["trace"], "slot_step_many",
                                   reduced["window"])
    chunks = readings.chunks_in(ctx)
    if not chunks or not programs:
        return None
    least = 0.0
    for chunk in chunks:
        ops, nbytes = ctx["ops"].decode_step(
            ctx["config"], readings.mean_step_lengths(chunk))
        least += max(ops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    step_s = (sum(m[2] for m in programs) / 1e9
              / (len(programs) * ctx["counters"]["chunk"]))
    return 100.0 * least / len(chunks) / step_s
