"""kernel.retention_state_roofline.serve: max(operations / peak FLOP/s, bytes / peak bytes/s) of the retention
state's update and query of a decode step over their device time in
the trace (the ops under ``ret.state``): the same work whatever
implements it, at the symmetric square's own size (``D = d(d+1)/2``
products a K/V head) whatever layout the program holds. Operations
and bytes are ``benchmark/ops``'s (``retention_state``) at the live
slots of each chunk the decoder dispatched inside the traced window,
by its own books (``readings.chunks_in``); the mean over the chunks.
At these sizes the bytes bound holds: each live slot's state is read
once and written once.

The device time is divided by the modules the scope table MATCHED
(``scopes.scoped``'s ``modules - unmatched``): an unmatched module's
ops carry no scope and add nothing to the time under ``ret.state``,
so dividing by all of them would read a share above the truth. A
program without such blocks has no such scope and no
``retention_state`` among its counts, and the reader returns None."""

LAYER = 'Kernels (decode-step program: matmul_any, slab attend)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'

SCOPE = "ret.state"


def read(ctx):
    from benchmark.harness import readings, scopes

    count = getattr(ctx["ops"], "retention_state", None)
    found = scopes.scoped(ctx, "slot_step_many", scopes.serve_part)
    steps = ctx["counters"].get("chunk")
    chunks = readings.chunks_in(ctx)
    if count is None or found is None or not steps or not chunks:
        return None
    matched = found["modules"] - found["unmatched"]
    spent_ns = sum(ns for (_, layer, _), ns in found["ops"].items()
                   if SCOPE in layer.split("/"))
    if not matched or not spent_ns:
        return None
    peaks, least = ctx["peaks"], 0.0
    for chunk in chunks:
        ops, nbytes = count(ctx["config"], len(chunk["lengths"]))
        least += max(ops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    step_s = spent_ns / 1e9 / (matched * steps)
    return 100.0 * least / len(chunks) / step_s
