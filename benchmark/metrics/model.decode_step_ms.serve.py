"""model.decode_step_ms.serve: Device time of the decode-chunk program (``XLA Modules`` events
named ``*slot_step_many*``) over the steps in a chunk."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import trace

    reduced = ctx["reduced"]
    chunks = trace.modules_named(reduced["trace"], "slot_step_many",
                                 reduced["window"])
    if not chunks:
        return None
    return (sum(m[2] for m in chunks) / 1e6
            / (len(chunks) * ctx["counters"]["chunk"]))
