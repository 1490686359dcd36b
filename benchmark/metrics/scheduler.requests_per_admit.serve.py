"""scheduler.requests_per_admit.serve: Prompts an admission dispatch carries, mean over the window:
live requests admitted over admission dispatches, summed over the
window's whole seconds of the driver's books (``/healthz``
``counters.serve_seconds``, ``admitted`` over ``admits``)."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'count'
SOURCE = 'program_counter'


def read(ctx):
    from benchmark.harness import driver_books

    found = driver_books.window(ctx)
    if found is None:
        return None
    rows = found[0]
    admits = driver_books.total(rows, "admits")
    if not admits:
        return None
    return driver_books.total(rows, "admitted") / admits
