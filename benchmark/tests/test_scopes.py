"""The scope and span readers (``harness/scopes.py`` and the twelve
``metrics/*`` files of PR 27) over a small recorded TPU capture and
the scope table the program gave for it
(``tools/record_scoped_trace.py``: a toy two-layer train sweep of 4
steps, three runs, and a toy two-block decoder, four chunks of 2).
"""

import json
import os

import pytest

from benchmark.harness import common, scopes, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
CAPTURE = os.path.join(DATA, "scoped_trace.xplane.pb")
BENCH = common.load_json("BENCHMARK.json")
NEW = ("model.forward_ms.train", "model.backward_ms.train",
       "model.update_ms.train", "model.unscoped_share.train",
       "engine.dispatch_ms.train", "engine.decision_wait_ms.train",
       "model.decode_cache_ms.serve", "model.decode_attend_ms.serve",
       "model.decode_matmul_ms.serve", "model.decode_head_ms.serve",
       "model.decode_unscoped_share.serve", "scheduler.books_ms.serve")
TRAIN = [name for name in NEW if name.startswith("model.")
         and name.endswith(".train")]
SERVE = [name for name in NEW if name.startswith("model.")
         and name.endswith(".serve")]


def recorded_tables():
    with open(os.path.join(DATA, "scoped_table.json")) as fin:
        raw = json.load(fin)
    return {fragment: [dict(table, instructions={
        name: (shape, op_name)
        for name, shape, op_name in table["instructions"]})
        for table in tables] for fragment, tables in raw.items()}


@pytest.fixture
def tables(monkeypatch):
    """The recorded scope table in the program's place."""
    found = recorded_tables()
    monkeypatch.setattr(scopes, "scope_table",
                        lambda function: found.get(function))
    return found


def context():
    return {"reduced": trace.reduce(CAPTURE),
            "counters": {"steps_per_train_sweep": 4, "chunk": 2}}


def read(name, ctx):
    return common.load_module("benchmark/metrics/%s.py" % name).read(ctx)


def op_ms(ctx, function):
    reduced = ctx["reduced"]
    modules = trace.modules_named(reduced["trace"], function,
                                  reduced["window"])
    ops = trace.ops_inside(reduced["trace"], modules)
    return len(modules), sum(op[2] for op in ops) / 1e6


@pytest.mark.parametrize("function,names,steps,runs", [
    ("train_sweep", TRAIN, 4, 3), ("slot_step_many", SERVE, 2, 4)])
def test_the_parts_and_the_unscoped_time_are_the_modules_op_time(
        tables, function, names, steps, runs):
    ctx = context()
    modules, total_ms = op_ms(ctx, function)
    assert modules == runs and total_ms > 0
    values = {name: read(name, ctx) for name in names}
    assert all(value is not None and value >= 0
               for value in values.values()), values
    share = [v for k, v in values.items() if "unscoped" in k][0]
    parts = sum(v for k, v in values.items() if "unscoped" not in k)
    per_step = total_ms / (modules * steps)
    assert parts + share / 100.0 * per_step == pytest.approx(per_step,
                                                             rel=1e-9)
    found = ctx["scoped"][function]
    assert found["unmatched"] == 0
    assert sum(found["parts"].values()) == pytest.approx(found["total"])
    assert found["total"] <= found["module_ns"]
    # the scopes reach real time: no part that ran is empty
    assert share < 50.0
    assert all(v > 0 for k, v in values.items() if "unscoped" not in k)


def test_backward_is_told_from_forward_by_the_op_name():
    assert scopes.train_part(
        "jit(f)/while/body/closed_call/jvp(fwd)/l0_conv/conv") == "forward"
    assert scopes.train_part(
        "jit(f)/while/body/transpose(jvp(fwd))/l0_conv/conv") == "backward"
    assert scopes.train_part("jit(f)/while/body/data/gather") == "forward"
    assert scopes.train_part("jit(f)/while/body/reduce/psum") == "backward"
    assert scopes.train_part("jit(f)/update/l0_conv/sub") == "update"
    assert scopes.train_part("jit(f)/while") == scopes.UNSCOPED
    assert scopes.train_part("") == scopes.UNSCOPED
    assert scopes.serve_part(
        "jit(s)/decode.dispatch/while/body/cache.read/slice") == "cache_read"
    assert scopes.serve_part("jit(s)/decode.dispatch/while") \
        == scopes.UNSCOPED


def test_a_module_that_matches_no_program_is_unscoped_not_dropped(
        tables, monkeypatch):
    """One instruction's output shape differs: the module's ops still
    count, every one of them as unscoped."""
    ctx = context()
    _, total_ms = op_ms(ctx, "train_sweep")
    (table,) = tables["train_sweep"]
    ran = {scopes.head_of(op[0])[0] for op in trace.ops_inside(
        ctx["reduced"]["trace"], trace.modules_named(
            ctx["reduced"]["trace"], "train_sweep"))}
    name = sorted(ran)[0]
    shape, op_name = table["instructions"][name]
    table["instructions"][name] = ("f32[7,7]{1,0}", op_name)
    assert read("model.unscoped_share.train", ctx) == pytest.approx(100.0)
    found = ctx["scoped"]["train_sweep"]
    assert found["unmatched"] == found["modules"] == 3
    assert found["total"] / 1e6 == pytest.approx(total_ms)
    # no module matched, so a part has nothing to read: None, not 0
    assert read("model.update_ms.train", ctx) is None


@pytest.mark.parametrize("given", [None, []])
def test_without_a_scope_table_every_scope_reader_returns_none(
        monkeypatch, given):
    monkeypatch.setattr(scopes, "scope_table", lambda function: given)
    ctx = context()
    assert [read(name, ctx) for name in TRAIN + SERVE] \
        == [None] * len(TRAIN + SERVE)


def test_a_program_without_the_scope_table_gives_none(monkeypatch):
    """The parent of the PR that brought it: ``xla_stats`` has no
    ``scope_table``."""
    from veles_tpu.observe import xla_stats

    monkeypatch.delattr(xla_stats, "scope_table", raising=False)
    assert scopes.scope_table("train_sweep") is None


def test_the_older_capture_has_nothing_for_the_new_readers(monkeypatch):
    monkeypatch.setattr(scopes, "scope_table", lambda function: None)
    ctx = {"reduced": trace.reduce(os.path.join(
        DATA, "small_trace.xplane.pb")),
        "counters": {"steps_per_train_sweep": 4, "chunk": 2}}
    assert {name: read(name, ctx) for name in NEW} \
        == dict.fromkeys(NEW)


def spans_context(spans, window=(0.0, 1e9)):
    return {"reduced": {"trace": {"spans": sorted(spans,
                                                  key=lambda s: s[1]),
                                  "devices": {}},
                        "window": window}, "counters": {}}


def test_self_time_is_a_span_less_what_the_spans_inside_it_cover():
    spans = [("engine.train_sweep", 100.0, 1000.0),
             ("inner.a", 200.0, 100.0), ("inner.b", 250.0, 150.0),
             ("outer.c", 50.0, 5000.0), ("engine.eval_sweep", 3000.0, 400.0)]
    ctx = spans_context(spans)
    assert scopes.self_ns(spans[0], spans) == 1000.0 - 200.0
    assert read("engine.dispatch_ms.train", ctx) \
        == pytest.approx((800.0 + 400.0) / 2 / 1e6)
    assert read("engine.dispatch_ms.train", spans_context([])) is None


def test_time_between_fences_is_summed_per_fence_and_the_median_taken():
    ms = 1e6
    spans = [("decode.dispatch", 0 * ms, 2 * ms),
             ("serve.drive_books", 3 * ms, 0.2 * ms),
             ("serve.drive_books", 90 * ms, 0.3 * ms),
             ("decode.dispatch", 100 * ms, 2 * ms),
             ("serve.drive_books", 103 * ms, 0.1 * ms),
             ("decode.dispatch", 200 * ms, 2 * ms),
             ("decode.dispatch", 300 * ms, 2 * ms),
             ("serve.drive_books", 303 * ms, 9.0 * ms)]  # past the last
    ctx = spans_context(spans)
    assert read("scheduler.books_ms.serve", ctx) \
        == pytest.approx(0.1)          # sums 0.5, 0.1, 0.0
    settle = [("engine.train_sweep", 0 * ms, 1 * ms),
              ("decision.settle", 5 * ms, 0.25 * ms),
              ("engine.train_sweep", 700 * ms, 1 * ms)]
    assert read("engine.decision_wait_ms.train",
                spans_context(settle)) == pytest.approx(0.25)
    assert read("engine.decision_wait_ms.train",
                spans_context(settle[:2])) is None


def test_the_new_metrics_name_layers_the_benchmark_already_has():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    before = {m["layer"] for m in BENCH["per_layer"]
              if m["name"] not in NEW}
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] \
        == list(NEW)
    for name in NEW:
        entry = entries[name]
        assert entry["layer"] in before, name
        assert entry["better"] == "lower"
        assert entry["source"] == (
            "device_trace" if name.startswith("model.")
            else "program_span")
        assert len(entry["workloads"]) == 1
