"""The four readers of the serving driver's per-second books
(``benchmark/harness/driver_books.py``) over a run's counters made by
hand: only the whole seconds inside the window count, a run without the
books reads nothing, and the worst second is the worst."""

import pytest

from benchmark.harness import common

READERS = ("scheduler.lane_yield.serve",
           "scheduler.requests_per_admit.serve",
           "scheduler.host_worst_second_ms.serve",
           "scheduler.gc_pause_ms.serve")

#: the window opens and closes inside a second: whole seconds 101..104
T_OPEN, T_CLOSE = 100.4, 105.7


def read(name, ctx):
    return common.load_module("benchmark/metrics/%s.py" % name).read(ctx)


def row(**fields):
    out = dict.fromkeys(
        ("admits", "admitted", "admit_rows", "lane_steps",
         "live_lane_steps", "delivered", "admit_ms", "dispatch_ms",
         "device_wait_ms", "idle_ms", "host_ms", "gc_ms", "gc_count",
         "worst_pass_ms"), 0)
    out.update(fields)
    return out


def ctx_of(books, t_open=T_OPEN, t_close=T_CLOSE):
    health = {"admitted": 10, "completed": 10}
    if books is not None:
        health["serve_seconds"] = {str(second): fields
                                   for second, fields in books.items()}
    return {"counters": {"health_counters": health, "t_open": t_open,
                         "t_close": t_close}}


def window_books():
    """Rows on both edges (100, 105) that must not count, and four whole
    seconds inside, one of them (103) with nothing booked."""
    edge = row(admits=50, admitted=50, lane_steps=1000, delivered=0,
               host_ms=900.0, gc_ms=900.0)
    return {
        100: edge,
        101: row(admits=4, admitted=6, lane_steps=128, delivered=96,
                 host_ms=20.0, gc_ms=1.5),
        102: row(admits=2, admitted=2, lane_steps=128, delivered=112,
                 host_ms=35.0, gc_ms=0.0),
        104: row(admits=2, admitted=4, lane_steps=64, delivered=48,
                 host_ms=30.0, gc_ms=10.25),
        105: edge,
    }


def test_the_readers_sum_the_whole_seconds_only():
    ctx = ctx_of(window_books())
    assert read("scheduler.lane_yield.serve", ctx) \
        == pytest.approx(100.0 * 256 / 320)
    assert read("scheduler.requests_per_admit.serve", ctx) \
        == pytest.approx(12 / 8)
    # second 104: 30 + 10.25 beats 102's 35 + 0
    assert read("scheduler.host_worst_second_ms.serve", ctx) \
        == pytest.approx(40.25)
    # 11.75 ms over the four whole seconds, the empty one included
    assert read("scheduler.gc_pause_ms.serve", ctx) \
        == pytest.approx(11.75 / 4)


@pytest.mark.parametrize("t_open, t_close, seconds", [
    (101.0, 105.0, [101, 102, 103, 104]),   # edges on whole seconds
    (101.0, 104.99, [101, 102, 103]),       # the last second not whole
    (100.001, 102.0, [101]),
])
def test_the_window_edges(t_open, t_close, seconds):
    from benchmark.harness import driver_books

    books = {second: row(admits=1, admitted=1, lane_steps=8,
                         delivered=second - 100)
             for second in range(99, 107)}
    rows, whole = driver_books.window(ctx_of(books, t_open, t_close))
    assert sorted(rows) == seconds and whole == len(seconds)
    assert read("scheduler.lane_yield.serve",
                ctx_of(books, t_open, t_close)) == pytest.approx(
        100.0 * sum(s - 100 for s in seconds) / (8 * len(seconds)))


@pytest.mark.parametrize("name", READERS)
def test_no_books_reads_nothing(name):
    # a program without the books, as the parent of the change is
    assert read(name, ctx_of(None)) is None
    # rows, but none inside the window
    assert read(name, ctx_of({99: row(admits=1, admitted=1, lane_steps=8,
                                      delivered=8)})) is None
    # no health counters at all
    assert read(name, {"counters": {"t_open": T_OPEN,
                                    "t_close": T_CLOSE}}) is None


def test_a_window_of_idle_seconds_reads_no_share():
    ctx = ctx_of({101: row(idle_ms=999.0, host_ms=1.0)})
    assert read("scheduler.lane_yield.serve", ctx) is None
    assert read("scheduler.requests_per_admit.serve", ctx) is None
    assert read("scheduler.host_worst_second_ms.serve", ctx) == 1.0
    assert read("scheduler.gc_pause_ms.serve", ctx) == 0.0


def test_the_fields_are_the_programs():
    from veles_tpu.observe.servescope import SECOND_FIELDS

    assert tuple(row()) == SECOND_FIELDS
