"""scheduler.lane_yield.serve: Share of the decode lane-steps dispatched in the window that became
answer tokens: answer tokens kept at collect over slots x chunk of every
decode dispatch, summed over the window's whole seconds of the driver's
books (``/healthz`` ``counters.serve_seconds``, ``delivered`` over
``lane_steps``). The rest are empty lanes, lanes stepping past an
answer's end inside a chunk, and the lag-1 tail."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'program_counter'


def read(ctx):
    from benchmark.harness import driver_books

    found = driver_books.window(ctx)
    if found is None:
        return None
    rows = found[0]
    lanes = driver_books.total(rows, "lane_steps")
    if not lanes:
        return None
    return 100.0 * driver_books.total(rows, "delivered") / lanes
