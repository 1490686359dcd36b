"""model.serve_mfu: Operations the traced window's work needs (``benchmark/ops``: the
matrices for every prompt and answer token, attention from the actual
lengths) over the window and the chip's bf16 peak. The work is the
decoder's own books (``readings.chunks_in``): the prompts admitted
inside the window, and of the decode steps dispatched there the share
that became answer tokens (a slot runs to the chunk's end past its
request's last token; those steps are not work)."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import readings

    chunks = readings.chunks_in(ctx)
    if not chunks or len(chunks) < 2:
        return None
    ops, config = ctx["ops"], ctx["config"]
    span = readings.traced_span(ctx)
    total = ops.prefill(
        config, [n for chunk in chunks for n in chunk["admitted"]])
    decode = 0
    for chunk in chunks:
        step_ops, _ = ops.decode_step(
            config, readings.mean_step_lengths(chunk))
        decode += chunk["steps"] * step_ops
    # what the decoder delivered between the first dispatch and the
    # last came from the chunks before the last
    lane_steps = sum(chunk["steps"] * len(chunk["lengths"])
                     for chunk in chunks[:-1])
    if not lane_steps:
        return None
    kept = (chunks[-1]["tokens_out"] - chunks[0]["tokens_out"]) \
        / lane_steps
    total += decode * kept
    return (100.0 * total / (span[1] - span[0])
            / ctx["peaks"]["bf16_flops_per_s"])
