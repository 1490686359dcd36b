"""kernel.prompt_attend_roofline.serve: Operations of the prompt attention that the splash kernel
(``ops/attention.grouped_attention``, calls named ``splash_mqa_fwd*``)
did in the traced window over the chip's bf16 peak, as a share of the
device time of those calls found by name in the trace. The operations
are ``benchmark/ops``'s (``prompt_attend``: every head's scores and
sums over the pairs each layer attends, the window layers' cut by
their window) at the bucket of each prompt admitted inside the traced
window by the decoder's own books (``readings.chunks_in``), one row a
prompt, for the buckets the kernel always takes (512 positions and
more at 128 heads; ``ops/attention.prompt_path``). A group's duplicate
rows and the smallest bucket's kernel calls count in the time and not
in the work, so the share errs low. A program without the kernel has
no such call, and the reader returns None."""

LAYER = 'Kernels (decode-step program: matmul_any, slab attend)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'

NAME = "splash_mqa_fwd"
#: the least bucket whose one row passes the kernel's rule at 128
#: heads (128 x 512^2 x 4 B of scores > 64 MiB)
KERNEL_BUCKET = 512


def bucket_of(n, max_len):
    """The decoder's admission bucket of an ``n``-token prompt."""
    bucket = 16
    while bucket < n:
        bucket *= 2
    return min(bucket, max_len)


def read(ctx):
    from benchmark.harness import readings, scopes

    count = getattr(ctx["ops"], "prompt_attend", None)
    reduced = ctx["reduced"]
    chunks = readings.chunks_in(ctx)
    if count is None or not chunks or reduced["window"] is None \
            or not reduced["trace"]["devices"]:
        return None
    lo, hi = reduced["window"]
    first = reduced["trace"]["devices"][min(reduced["trace"]["devices"])]
    spent_ns = sum(duration for text, start, duration in first["ops"]
                   if lo <= start < hi
                   and scopes.head_of(text)[0].startswith(NAME))
    max_len = ctx["config"]["serving"]["max_len"]
    buckets = [bucket_of(n, max_len) for chunk in chunks
               for n in chunk["admitted"]]
    ops = sum(count(ctx["config"], b) for b in buckets
              if b >= KERNEL_BUCKET)
    if not spent_ns or not ops:
        return None
    return 100.0 * ops / ctx["peaks"]["bf16_flops_per_s"] \
        / (spent_ns / 1e9)
