"""kernel.kda_state_roofline.serve: max(operations / peak FLOP/s, bytes / peak bytes/s) of the
delta rule's state in a decode step over its device time in the trace
(the ops under ``kda.state``): the recurrence's operations (7 d^2 a head
a token) and each live slot's float32 state read once and written once
with its q, k, v, decays and write strengths, whatever implements it.
Operations and bytes are ``benchmark/ops``'s (``kda_state``) at the
live slots of each chunk the decoder dispatched inside the traced
window, by its own books (``readings.chunks_in``); the mean over the
chunks. At these sizes the bytes bound holds.

The device time is divided by the modules the scope table MATCHED
(``scopes.scoped``'s ``modules - unmatched``): an unmatched module's
ops carry no scope and add nothing to the time under ``kda.state``, so
dividing by all of them would read a share above the truth. A program
without such blocks has no such scope and no ``kda_state`` among its
counts, and the reader returns None."""

LAYER = 'Kernels (decode-step program: matmul_any, slab attend)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'

SCOPE = "kda.state"


def read(ctx):
    from benchmark.harness import readings, scopes

    count = getattr(ctx["ops"], "kda_state", None)
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
