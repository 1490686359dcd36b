"""kernel.window_attend_roofline.serve: max(operations / peak FLOP/s, bytes / peak bytes/s) of a decode
step's attend in the window and global layers over their device time in
the trace (the ops under ``swa.ring`` and ``nope.attend``): the same
work whatever implements it. Operations and bytes are
``benchmark/ops``'s (``window_attend``: every head's scores and sums
over the positions each live slot attends, a window layer's last
``min(length, window)`` and the global layer's whole sequence, each K
and V row read once) at the lengths the live slots held at each chunk
the decoder dispatched inside the traced window, by its own books
(``readings.chunks_in``); the mean over the chunks. At these sizes
the bytes bound holds.

The device time is divided by the modules the scope table MATCHED
(``scopes.scoped``'s ``modules - unmatched``): an unmatched module's
ops carry no scope and add nothing to the time under these scopes, so
dividing by all of them would read a share above the truth. A program
without such layers has no such scope and no ``window_attend`` among
its counts, and the reader returns None."""

LAYER = 'Kernels (decode-step program: matmul_any, slab attend)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'

SCOPES = ("swa.ring", "nope.attend")


def read(ctx):
    from benchmark.harness import readings, scopes

    count = getattr(ctx["ops"], "window_attend", None)
    found = scopes.scoped(ctx, "slot_step_many", scopes.serve_part)
    steps = ctx["counters"].get("chunk")
    chunks = readings.chunks_in(ctx)
    if count is None or found is None or not steps or not chunks:
        return None
    matched = found["modules"] - found["unmatched"]
    spent_ns = sum(ns for (_, layer, _), ns in found["ops"].items()
                   if any(name in layer.split("/") for name in SCOPES))
    if not matched or not spent_ns:
        return None
    peaks, least = ctx["peaks"], 0.0
    for chunk in chunks:
        ops, nbytes = count(ctx["config"],
                            readings.mean_step_lengths(chunk))
        least += max(ops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    step_s = spent_ns / 1e9 / (matched * steps)
    return 100.0 * least / len(chunks) / step_s
