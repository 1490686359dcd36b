"""The serving driver's books by wall second, as the program keeps them
(``veles_tpu/observe/servescope.py`` ``SECOND_FIELDS``) and ``/healthz``
hands them over (``counters.serve_seconds``: ``{second: {field:
value}}``), cut to a run's window for the ``scheduler.*`` readers.

A window opens and closes inside a second, so only the whole seconds
inside ``[t_open, t_close)`` count: the seconds ``s`` with ``t_open <=
s`` and ``s + 1 <= t_close``. A whole second in which nothing was
booked has no row. A program without the books (``/healthz`` lacks
``serve_seconds``) gives None, and so does a window none of whose
seconds has a row.
"""

import math


def window(ctx):
    """``(rows, seconds)``: the rows of the window's whole seconds, by
    second, and how many whole seconds the window holds; or None."""
    counters = ctx["counters"]
    books = (counters.get("health_counters") or {}).get("serve_seconds")
    t_open, t_close = counters.get("t_open"), counters.get("t_close")
    if not isinstance(books, dict) or t_open is None or t_close is None:
        return None
    first, end = math.ceil(t_open), math.floor(t_close)
    rows = {second: books[str(second)] for second in range(first, end)
            if isinstance(books.get(str(second)), dict)}
    return (rows, end - first) if rows else None


def total(rows, field):
    """``field`` summed over ``rows``."""
    return sum(row.get(field, 0) for row in rows.values())
