"""The routed experts' streaming kernels and the rule that chooses one.

``ops/moe.streamed_experts`` (a Pallas TPU kernel: each touched expert
once, its matrices whole, rows and result resident) and
``ops/moe.tiled_experts`` (the same with the rows and the result a
tile at a time: any number of rows) in interpret mode, at lane-aligned
toy widths, against ``jax.lax.ragged_dot`` (the third tiling of the
same grouped products) and against a loop over the experts. The rule
(``ops/moe.expert_path``) reads the platform and static shapes; the
tests steer the platform (``moe.on_tpu``) and never the path itself,
so what they run is what the TPU's rule picks, interpreted.
"""

import importlib.util
import json
import os
import urllib.request

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNT, WIDTH, INNER, TOP_K = 8, 128, 256, 2
CLOSE = dict(rtol=1e-4, atol=1e-5)


def _experts(count=COUNT, width=WIDTH, inner=INNER, dtype=jnp.float32):
    rng = numpy.random.RandomState(count + width + inner)

    def leaf(*shape):
        return jnp.asarray(rng.randn(*shape) / numpy.sqrt(shape[1]), dtype)

    return {"w_gate": leaf(count, width, inner),
            "w_up": leaf(count, width, inner),
            "w_down": leaf(count, inner, width)}


@pytest.fixture(scope="module")
def experts():
    return _experts()


@pytest.fixture
def on_the_chip(monkeypatch):
    """The rule as the TPU reads it; the kernel itself still resolves
    interpret mode from the real platform."""
    monkeypatch.setattr(moe, "on_tpu", lambda: True)


def _tokens(seed, n, top_k=TOP_K, count=COUNT, never=None, always=None):
    """``(h, chosen, weights)``: ``n`` tokens, each with ``top_k``
    distinct experts; ``never`` is chosen by none, ``always`` by
    all."""
    rng = numpy.random.RandomState(seed)
    scores = rng.rand(n, count)
    if never is not None:
        scores[:, never] = -1.0
    if always is not None:
        scores[:, always] = 2.0
    chosen = numpy.argsort(-scores, -1)[:, :top_k]
    return (jnp.asarray(rng.randn(n, WIDTH), jnp.float32),
            jnp.asarray(chosen, jnp.int32),
            jnp.asarray(rng.rand(n, top_k) + 0.1, jnp.float32))


def _loop(h, chosen, weights, experts, held=None, live=None):
    """Every held expert over every token, one at a time."""
    first = 0 if held is None else held[0]
    if live is not None:
        weights = jnp.where(live[:, None], weights, 0.0)
    y = jnp.zeros_like(h)
    for e in range(experts["w_gate"].shape[0]):
        share = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        y = y + share[:, None] * moe.swiglu(
            h, jax.tree.map(lambda w: w[e], experts))
    return y


def _both(h, chosen, weights, experts, monkeypatch, path="streamed",
          **kwargs):
    """``routed_experts`` as the CPU's rule runs it (``ragged_dot``)
    and as the TPU's rule does (the kernel, interpreted)."""
    rows = chosen.size
    assert moe.expert_path(rows, experts) == "grouped"
    grouped = moe.routed_experts(h, chosen, weights, experts, **kwargs)
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    assert moe.expert_path(rows, experts) == path
    streamed = moe.routed_experts(h, chosen, weights, experts, **kwargs)
    return grouped, streamed


@pytest.mark.parametrize("case", [
    "uneven", "not_live", "rows_8", "rows_256",
    # over STREAM_MAX_ROWS, the tiled kernel: rows that are no whole
    # number of tiles, an expert with every row and one with none,
    # ``live`` leaving tokens out, a tile that every expert shares
    "tiled_rows_602", "tiled_uneven", "tiled_not_live", "tiled_rows_1024"])
def test_the_streamed_experts_agree_with_ragged_dot_and_the_loop(
        experts, monkeypatch, case):
    """(a) an expert with no row and one with every row; (b) ``live``
    leaving slots out; (d) 8 and 256 rows: the same ``y`` from the
    kernel, from ``ragged_dot`` and from the loop, the same ``load``."""
    live, path = None, "tiled" if case.startswith("tiled") else "streamed"
    if case == "uneven":
        h, chosen, weights = _tokens(1, 41, never=3, always=5)
    elif case == "not_live":
        h, chosen, weights = _tokens(2, 6)
        live = jnp.asarray([True, False, True, True, False, False])
    elif case == "tiled_uneven":
        h, chosen, weights = _tokens(7, 290, never=3, always=5)
    elif case == "tiled_not_live":
        h, chosen, weights = _tokens(8, 300)
        live = jnp.asarray(numpy.random.RandomState(8).rand(300) > 0.4)
    else:
        h, chosen, weights = _tokens(3, int(case.split("_")[-1]) // TOP_K)
    (grouped, load), (streamed, load_streamed) = _both(
        h, chosen, weights, experts, monkeypatch, path, live=live)
    want = _loop(h, chosen, weights, experts, live=live)
    numpy.testing.assert_allclose(numpy.asarray(streamed),
                                  numpy.asarray(want), **CLOSE)
    numpy.testing.assert_allclose(numpy.asarray(streamed),
                                  numpy.asarray(grouped), **CLOSE)
    numpy.testing.assert_array_equal(numpy.asarray(load_streamed),
                                     numpy.asarray(load))
    alive = numpy.ones(len(h), bool) if live is None \
        else numpy.asarray(live)
    numpy.testing.assert_array_equal(
        numpy.asarray(load), numpy.bincount(
            numpy.asarray(chosen)[alive].ravel(), minlength=COUNT))
    if case.endswith("uneven"):
        assert load[3] == 0 and load[5] == len(h)
    if live is not None:
        assert not numpy.asarray(streamed)[~alive].any()


@pytest.mark.parametrize("path", ["grouped", "streamed", "tiled"])
def test_the_held_quarters_add_up_to_the_layer(experts, monkeypatch,
                                               path):
    """(c) each quarter of the experts as a share of its own."""
    h, chosen, weights = _tokens(4, 290 if path == "tiled" else 29)
    if path != "grouped":
        monkeypatch.setattr(moe, "on_tpu", lambda: True)
    whole, load = moe.routed_experts(h, chosen, weights, experts)
    total, loads = jnp.zeros_like(h), []
    for first in range(0, COUNT, COUNT // 4):
        held = jax.tree.map(lambda w: w[first:first + COUNT // 4],
                            experts)
        assert moe.expert_path(chosen.size, held) == path
        part, part_load = moe.routed_experts(
            h, chosen, weights, held, held=(first, COUNT // 4))
        numpy.testing.assert_allclose(
            numpy.asarray(part), numpy.asarray(_loop(
                h, chosen, weights, held, held=(first, COUNT // 4))),
            **CLOSE)
        total = total + part
        loads.append(numpy.asarray(part_load))
    numpy.testing.assert_allclose(numpy.asarray(total),
                                  numpy.asarray(whole), **CLOSE)
    numpy.testing.assert_array_equal(numpy.concatenate(loads),
                                     numpy.asarray(load))


@pytest.mark.parametrize("path, tokens", [("streamed", 19),
                                          ("tiled", 270)])
def test_bfloat16_operands_accumulate_and_gate_in_float32(
        on_the_chip, path, tokens):
    """As served: bfloat16 rows and matrices; the kernel's products
    stand as close to the float32 loop as ``ragged_dot``'s do."""
    experts = _experts(dtype=jnp.bfloat16)
    h, chosen, weights = _tokens(5, tokens)
    h = h.astype(jnp.bfloat16)
    assert moe.expert_path(chosen.size, experts) == path
    got, _ = moe.routed_experts(h, chosen, weights, experts)
    assert got.dtype == jnp.bfloat16
    want = _loop(h.astype(jnp.float32), chosen, weights,
                 jax.tree.map(lambda w: w.astype(jnp.float32), experts))
    gap = numpy.abs(numpy.asarray(got, numpy.float32)
                    - numpy.asarray(want)).max()
    assert gap < 0.02 * numpy.abs(numpy.asarray(want)).max(), gap


@pytest.mark.parametrize("rows, calls", [
    (moe.STREAM_MAX_ROWS, ("moe_streamed_experts", "moe_tiled_experts")),
    (moe.STREAM_MAX_ROWS + 1, ("moe_tiled_experts",
                               "moe_streamed_experts")),
])
def test_one_row_over_the_threshold_takes_the_tiled_kernel(
        experts, on_the_chip, rows, calls):
    """(d) the program that is traced holds the one kernel and not the
    other, never ``ragged_dot``, and gives the loop's numbers either
    way."""
    h, chosen, weights = _tokens(6, rows, top_k=1)
    traced = str(jax.make_jaxpr(moe.routed_experts)(
        h, chosen, weights, experts))
    assert calls[0] in traced and calls[1] not in traced
    assert "ragged_dot" not in traced
    got, load = moe.routed_experts(h, chosen, weights, experts)
    numpy.testing.assert_allclose(
        numpy.asarray(got),
        numpy.asarray(_loop(h, chosen, weights, experts)), **CLOSE)
    assert int(load.sum()) == rows


@pytest.mark.parametrize("platform, width, inner, rows, sharded, path", [
    ("cpu", 128, 256, 16, False, "grouped"),
    ("tpu", 128, 256, 16, False, "streamed"),
    ("tpu", 128, 256, moe.STREAM_MAX_ROWS + 1, False, "tiled"),
    ("tpu", 128, 256, 32768, False, "tiled"),
    ("cpu", 128, 256, 32768, False, "grouped"),
    ("tpu", 96, 256, 16, False, "grouped"),
    ("tpu", 128, 200, 16, False, "grouped"),
    ("tpu", 128, 256, 16, True, "grouped"),
    ("tpu", 96, 256, 4096, False, "grouped"),
    ("tpu", 128, 200, 4096, False, "grouped"),
    ("tpu", 128, 256, 4096, True, "grouped"),
])
def test_the_rule_reads_platform_widths_rows_and_sharding(
        monkeypatch, platform, width, inner, rows, sharded, path):
    if platform == "tpu":
        monkeypatch.setattr(moe, "on_tpu", lambda: True)
    experts = _experts(width=width, inner=inner)
    if sharded:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        mesh = Mesh(numpy.asarray(jax.devices()[:2]), ("expert",))
        experts = jax.device_put(
            experts, NamedSharding(mesh, PartitionSpec("expert")))
    assert moe.expert_path(rows, experts) == path
    # shapes alone say as much: what the decoder asks before a dispatch
    if not sharded:
        assert moe.expert_path(rows, jax.eval_shape(
            lambda: experts)) == path


@pytest.mark.parametrize("load, n_rows", [
    ([0, 3, 0, 0, 2, 0, 0, 1], 6),
    ([0, 0, 0, 0, 0, 0, 0, 0], 4),
    ([1, 1, 1, 1, 1, 1, 1, 1], 8),
    ([0, 0, 0, 0, 0, 0, 0, 64], 64),
    ([2, 0, 1, 0, 0, 0, 0, 0], 3),
])
def test_the_visit_table_walks_the_touched_experts_once(load, n_rows):
    """Touched experts first, in their order, with their row ranges; a
    visit past the last repeats its expert (no new block to fetch) and
    has no rows."""
    ids, first, n = (numpy.asarray(a) for a in moe.visit_table(
        jnp.asarray(load, jnp.int32), n_rows))
    touched = [e for e, rows in enumerate(load) if rows]
    visits = min(len(load), n_rows)
    assert len(ids) == len(first) == len(n) == visits
    assert ids[:len(touched)].tolist() == touched
    assert n[:len(touched)].tolist() == [load[e] for e in touched]
    assert first[:len(touched)].tolist() == [
        sum(load[:e]) for e in touched]
    assert not n[len(touched):].any()
    assert set(ids[len(touched):].tolist()) <= {
        touched[-1] if touched else 0}


@pytest.mark.parametrize("load, n_rows, tile", [
    ([0, 3, 0, 0, 2, 0, 0, 1], 16, 16),
    ([0, 0, 0, 0, 0, 0, 0, 0], 32, 16),
    ([5, 5, 5, 5, 5, 5, 5, 5], 48, 16),
    ([0, 0, 0, 0, 0, 0, 0, 64], 64, 16),
    ([16, 16, 0, 32, 0, 0, 0, 0], 64, 16),
    ([1, 40, 0, 0, 7, 0, 20, 0], 96, 32),
    ([300, 0, 1, 0, 0, 0, 0, 211], 512, 128),
])
def test_the_tile_table_covers_each_experts_rows_tile_by_tile(
        load, n_rows, tile):
    """Each touched expert, in order, has one item for every tile its
    rows reach and no other; an item's rows are its expert's; an item
    past the last repeats its expert and tile and has no rows."""
    ids, tiles, first, end = (numpy.asarray(a) for a in moe.tile_table(
        jnp.asarray(load, jnp.int32), n_rows, tile))
    assert len(ids) == n_rows // tile + min(len(load), n_rows) - 1
    stops = numpy.cumsum(load)
    want = [(e, t) for e, rows in enumerate(load) if rows
            for t in range((stops[e] - rows) // tile,
                           (stops[e] - 1) // tile + 1)]
    live = len(want)
    assert list(zip(ids[:live], tiles[:live])) == want
    assert first[:live].tolist() == [stops[e] - load[e] for e, _ in want]
    assert end[:live].tolist() == [stops[e] for e, _ in want]
    assert not first[live:].any() and not end[live:].any()
    assert set(zip(ids[live:], tiles[live:])) <= {
        want[-1] if want else (len(load) - 1, 0)}
    # every row of every expert lies in exactly one of its items
    covered = numpy.zeros(n_rows, int)
    for e, t, lo, hi in zip(ids, tiles, first, end):
        covered[max(lo, t * tile):min(hi, (t + 1) * tile)] += 1
    assert covered[:stops[-1]].tolist() == [1] * stops[-1]
    assert not covered[stops[-1]:].any()


@pytest.mark.parametrize("fault", [None, "boundary"])
def test_a_boundary_tiles_rows_given_to_the_neighbour_are_caught(
        experts, fault):
    """The comparison the other tests rest on sees a tile that two
    experts share cut at the wrong row: three rows of the one computed
    by the other."""
    rng = numpy.random.RandomState(9)
    load = jnp.asarray([40, 0, 30, 58, 0, 0, 64, 0], jnp.int32)
    rows = jnp.asarray(rng.randn(192, WIDTH), jnp.float32)
    table = [numpy.array(a) for a in moe.tile_table(load, 192, 64)]
    if fault:
        ids, tiles, first, end = table
        # the second item of a tile two experts share
        at = next(i for i in range(1, len(ids)) if tiles[i] == tiles[i - 1]
                  and ids[i] != ids[i - 1])
        end[ids == ids[at - 1]] -= 3
        first[ids == ids[at]] -= 3
    got = moe.tiled_experts(rows, tuple(jnp.asarray(a) for a in table),
                            experts, tile=64)
    want = moe.grouped_experts(rows, load, experts)
    gap = numpy.abs(numpy.asarray(got) - numpy.asarray(want)).max(-1)
    if fault:
        assert (gap > 0.1).sum() == 3 and (gap[37:40] > 0.1).all(), gap
    else:
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(want), **CLOSE)


# -- the decoder books the path of every dispatch ------------------------------

def _reference():
    spec = importlib.util.spec_from_file_location(
        "joyai_reference_streamed", os.path.join(
            ROOT, "benchmark/references/joyai-llm-flash.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def toy_model():
    """The benchmark configuration's rehearsal model with lane-wide
    expert matrices (128 x 128), in float32."""
    with open(os.path.join(
            ROOT, "benchmark/configs/joyai-llm-flash.json")) as fin:
        config = json.load(fin)
    config.update(config["rehearsal"])
    config.update(hidden_size=128, moe_intermediate_size=128)
    params, table = _reference().init_params(11, config)
    return config, jax.tree.map(lambda a: a.astype(jnp.float32),
                                (params, table))


def _serve(params, table, config, prompts):
    from veles_tpu.serving import ContinuousDecoder

    decoder = ContinuousDecoder(params, table, config["n_head"], slots=4,
                                max_len=64, n_tokens=5)
    rids = [decoder.submit(prompt) for prompt in prompts]
    decoder.drain_pipelined(4)
    return decoder, [decoder.results[rid] for rid in rids]


def test_the_decoder_books_the_path_of_every_dispatch(toy_model,
                                                      monkeypatch):
    """On the CPU every dispatch is grouped. With the platform steered
    and the threshold at this toy's chunk (4 slots x top-4 = 16 rows),
    every chunk streams, every admission (a bucket of 16 tokens or
    more) takes the tiled kernel, and the tokens are the same."""
    config, (params, table) = toy_model
    rng = numpy.random.RandomState(12)
    prompts = [rng.randint(0, config["vocab_size"], n).tolist()
               for n in (5, 20, 9, 33, 7)]
    jax.clear_caches()
    decoder, want = _serve(params, table, config, prompts)
    counts = decoder.dispatch_counts
    assert decoder.moe_counters()["moe_expert_path"] == {
        "streamed": 0, "tiled": 0,
        "grouped": counts["admit"] + counts["chunk"]}
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    monkeypatch.setattr(moe, "STREAM_MAX_ROWS",
                        4 * config["num_experts_per_tok"])
    jax.clear_caches()
    try:
        decoder, got = _serve(params, table, config, prompts)
    finally:
        jax.clear_caches()
    counts = decoder.dispatch_counts
    assert counts["admit"] >= 2 and counts["chunk"] >= 2
    assert decoder.moe_counters()["moe_expert_path"] == {
        "streamed": counts["chunk"], "tiled": counts["admit"],
        "grouped": 0}
    assert got == want


@pytest.fixture
def observability(tmp_path, monkeypatch):
    """A recorder of its own, the tracer and the registry on; what
    other suites also touch is put back."""
    from veles_tpu.core import logger as logger_mod
    from veles_tpu.core.logger import EventRecorder
    from veles_tpu.observe.metrics import get_metrics_registry
    from veles_tpu.observe.tracing import get_tracer

    events_path = str(tmp_path / "events.jsonl")
    recorder = EventRecorder()
    recorder.open(events_path)
    monkeypatch.setattr(logger_mod, "_event_recorder", recorder)
    tracer, registry = get_tracer(), get_metrics_registry()
    was_traced, was_metered = tracer.enabled, registry.enabled
    tracer.enable()
    registry.reset()
    registry.enable()
    yield events_path
    recorder.close()
    tracer.enabled = was_traced
    registry.reset()
    registry.enabled = was_metered


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_healthz_metrics_and_spans_say_the_path(
        toy_model, observability, tmp_path, monkeypatch, platform):
    """Where the rule cannot take a kernel every dispatch says
    ``grouped``; with the platform steered and the threshold at this
    toy's chunk (16 rows), an admission says ``tiled`` and a chunk
    ``streamed``, in ``/healthz``, in ``/metrics`` and on the spans."""
    from veles_tpu.observe.trace_export import export_chrome_trace
    from veles_tpu.serving import GenerateAPI

    config, (params, table) = toy_model
    if platform == "tpu":
        monkeypatch.setattr(moe, "on_tpu", lambda: True)
        monkeypatch.setattr(moe, "STREAM_MAX_ROWS",
                            4 * config["num_experts_per_tok"])
    jax.clear_caches()
    api = GenerateAPI(params, table, config["n_head"], slots=4,
                      max_len=64, n_tokens=5, chunk=2, port=0)
    api.start()
    try:
        url = "http://127.0.0.1:%d" % api.port
        request = urllib.request.Request(
            url + "/generate", data=json.dumps(
                {"tokens": [1, 2, 3, 4, 5]}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(request, timeout=120) as answer:
            assert len(json.loads(answer.read())["tokens"]) == 5
        with urllib.request.urlopen(url + "/healthz", timeout=10) as got:
            health = json.loads(got.read())
        with urllib.request.urlopen(url + "/metrics", timeout=10) as got:
            metrics = got.read().decode()
        counts = dict(api.decoder.dispatch_counts)
    finally:
        api.stop()
        jax.clear_caches()
    by_span = {"decode.admit": "tiled", "decode.dispatch": "streamed"} \
        if platform == "tpu" else dict.fromkeys(
            ("decode.admit", "decode.dispatch"), "grouped")
    want = {"streamed": 0, "tiled": 0, "grouped": 0}
    want[by_span["decode.admit"]] += counts["admit"]
    want[by_span["decode.dispatch"]] += counts["chunk"]
    assert counts["admit"] and counts["chunk"]
    said = health["counters"]["moe_expert_path"]
    assert said == want
    for path, n in want.items():
        assert ('veles_moe_expert_dispatches_total{path="%s"} %d'
                % (path, n) in metrics) == bool(n)
    # the three numbers of a moe_by_lanes row stay what they were
    assert all(len(row) == 3
               for row in health["counters"]["moe_by_lanes"].values())
    out = str(tmp_path / "trace.json")
    export_chrome_trace(observability, out)
    with open(out) as fin:
        spans = [e for e in json.load(fin)["traceEvents"]
                 if e["name"] in ("decode.admit", "decode.dispatch")]
    assert {e["name"] for e in spans} == {"decode.admit",
                                          "decode.dispatch"}
    assert all(e["args"]["moe_expert_path"] == by_span[e["name"]]
               for e in spans)
