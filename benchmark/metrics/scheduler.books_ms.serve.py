"""scheduler.books_ms.serve: Host time the driver spends on its books per chunk dispatched,
median: the program's own span ``serve.drive_books`` (``serving.py``
``_drive``: staged queue, deadlines, finished requests, governor and
rollout ticks), summed between one ``decode.dispatch`` and the next."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'program_span'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.median_ms_between(ctx, "serve.drive_books",
                                    "decode.dispatch")
