"""scheduler.gc_pause_ms.serve: Milliseconds a second the serving process spent in Python's garbage
collector over the window: the collections' pauses (any thread's,
``gc_ms``) summed over the window's whole seconds of the driver's books
(``/healthz`` ``counters.serve_seconds``), over the number of those
seconds."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'program_counter'


def read(ctx):
    from benchmark.harness import driver_books

    found = driver_books.window(ctx)
    if found is None:
        return None
    rows, seconds = found
    return driver_books.total(rows, "gc_ms") / seconds
