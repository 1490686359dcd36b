"""The reduction of the recorded trace gives the numbers written down
for it.

``data/small_trace.xplane.pb`` was recorded on a TPU v5 lite (PR 26,
under ``jax.profiler.start_trace``/``stop_trace`` with
``python_tracer_level`` 0, as ``common.TracedWindow`` captures): four executions of one
jitted convolution + matrix product step, each under a ``unit.run``
span, with a 2 ms sleep under no span after steps 0 and 2 and a 4 ms
sleep under a ``decode.collect`` span after steps 1 and 3. Read by
hand from the capture: the four ``jit_step`` modules take 46,668 +
46,671 + 46,866 + 46,673 ns = 186,878 ns; the ops inside them cover
186,774 ns; first op to last op is 12,104,363 ns.
"""

import os

import pytest

from benchmark.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(SMALL)


def test_window_and_busy(reduced):
    assert reduced["window"] == (44270061.0, 56374424.0)
    assert reduced["window_s"] == pytest.approx(0.012104363, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.000186774, rel=1e-6)
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.98457, abs=1e-5)


def test_modules_and_their_ops(reduced):
    modules = trace.modules_named(reduced["trace"], "jit_step")
    assert [m[2] for m in modules] == [46668.0, 46671.0, 46866.0,
                                       46673.0]
    inside = trace.ops_inside(reduced["trace"], modules)
    assert len(inside) == 60
    assert sum(op[2] for op in inside) == pytest.approx(186774.0)
    assert trace.modules_named(reduced["trace"], "no_such") == []
    # a module's event reaches a little past its ops on both sides,
    # so the first and the last stick out of a window that runs from
    # the first op to the last: they count all the same (by their
    # midpoint), and a window over the first two counts two
    assert modules[0][1] < reduced["window"][0]
    assert trace.modules_named(reduced["trace"], "jit_step",
                               reduced["window"]) == modules
    half = (reduced["window"][0], modules[2][1])
    assert trace.modules_named(reduced["trace"], "jit_step",
                               half) == modules[:2]


def test_op_table_names_the_convolution_first(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert ops[0][0] == "fusion.8 fusion bf16[32,28,28,64]"
    assert ops[0][1] == pytest.approx(9.2876e-05, rel=1e-6)
    assert ops[1][0] == "copy copy bf16[32,28,28,64]"
    assert len(ops) <= 10


def test_gaps_go_to_the_span_that_covers_them(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # the two 4 ms sleeps ran under decode.collect; the 2 ms sleeps and
    # the stretch before the host's first call under no span
    assert gaps["decode.collect"] == pytest.approx(0.005351736,
                                                   rel=1e-6)
    assert gaps["no span"] == pytest.approx(0.006565783, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-9)


def test_parse_op():
    text = ("%fusion.8 = bf16[32,28,28,64]{3,0,2,1:T(8,128)(2,1)S(1)} "
            "fusion(bf16[32,28,28,64]{3,0,2,1:T(8,128)(2,1)S(1)} "
            "%copy-done, bf16[3,3,64,64]{3,2,1,0:T(8,128)(2,1)} %w.1), "
            "kind=kOutput, calls=%fused_computation.4")
    op = trace.parse_op(text)
    assert op["name"] == "fusion.8" and op["kind"] == "fusion"
    assert op["fusion_kind"] == "kOutput"
    assert op["shapes"] == [("bf16", (32, 28, 28, 64)),
                            ("bf16", (32, 28, 28, 64)),
                            ("bf16", (3, 3, 64, 64))]
    loop = trace.parse_op(
        "%while.3 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, "
        "f32[8]{0}) %tuple), condition=%cond, body=%body")
    assert loop["kind"] == "while"


def test_merged_and_gaps():
    merged = trace.merged([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert trace.gaps(merged, (0, 10)) == [(3, 5), (8, 10)]
    spans = [("a.b", 0.0, 4000.0), ("c.d", 4000.0, 100000.0)]
    assert trace.attribute_gaps([(0.0, 4000.0), (4000.0, 54000.0),
                                 (200000.0, 300000.0),
                                 (300000.0, 300500.0)], spans) == [
        ["no span", 1e-4], ["c.d", 5e-5], ["a.b", 4e-6],
        ["gaps under 2 us", 5e-7]]
