"""scheduler.host_worst_second_ms.serve: The serving driver's worst whole second of the window for host
time: milliseconds in the gaps between its device-facing calls (``host_ms``)
plus garbage-collection pauses (``gc_ms``), the largest over the window's
whole seconds of the driver's books (``/healthz``
``counters.serve_seconds``)."""

LAYER = 'Scheduler (serving.py ContinuousDecoder)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'program_counter'


def read(ctx):
    from benchmark.harness import driver_books

    found = driver_books.window(ctx)
    if found is None:
        return None
    return max(row.get("host_ms", 0) + row.get("gc_ms", 0)
               for row in found[0].values())
