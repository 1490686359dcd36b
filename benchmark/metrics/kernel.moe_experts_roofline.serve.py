"""kernel.moe_experts_roofline.serve: max(operations / peak FLOP/s, bytes / peak bytes/s) of the routed
experts' products of a decode step over their device time in the trace
(``model.decode_moe_experts_ms.serve``): the same work whatever
implements it. Operations and bytes are ``benchmark/ops``'s
(``expert_products``) at the assignments and the distinct experts the
program counted: the decoder books, by the number of live slots a
chunk ran with, the expert-block steps, their assignments and the
experts they touched (``/healthz`` ``moe_by_lanes``), and each chunk
dispatched inside the traced window (``readings.chunks_in``) takes the
means of the chunks that ran with as many slots as it did. At these
sizes the bytes bound holds: a touched expert is read once."""

LAYER = 'Kernels (decode-step program: matmul_any, slab attend)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes, readings

    spent_ms = moe_scopes.inner_ms(ctx, "moe.experts")
    books = (ctx["counters"].get("health_counters") or {}) \
        .get("moe_by_lanes")
    chunks = readings.chunks_in(ctx)
    ops = getattr(ctx["ops"], "expert_products", None)
    if not spent_ms or not books or not chunks or ops is None:
        return None
    peaks, least, counted = ctx["peaks"], 0.0, 0
    blocks = ctx["ops"].layers(ctx["config"])[1]
    for chunk in chunks:
        row = books.get(str(len(chunk["lengths"])))
        if not row or not row[0]:
            continue
        n_ops, nbytes = ops(ctx["config"], row[1] / row[0],
                            row[2] / row[0])
        least += blocks * max(n_ops / peaks["bf16_flops_per_s"],
                              nbytes / peaks["hbm_bytes_per_s"])
        counted += 1
    if not counted:
        return None
    return 100.0 * least / counted / (spent_ms / 1e3)
