"""The routed experts' streaming kernels and the rule that chooses one.

``ops/moe.streamed_experts`` (a Pallas TPU kernel: each touched expert
once, rows and result resident) and ``ops/moe.tiled_experts`` (the same
with the rows and the result a tile at a time: any number of rows) in
interpret mode, at lane-aligned toy widths, against
``jax.lax.ragged_dot`` (the third tiling of the same grouped products)
and against a loop over the experts; each with its experts whole and in
slices of their inner width. The rule (``ops/moe.expert_plan``) reads
the platform, the chip's VMEM and static shapes; the tests steer the
platform (``moe.on_tpu``) and the chip (``moe.device_kind``, with a
small ``moe.VMEM_MIB`` where they want slices) and never the path
itself, so what they run is what the TPU's rule picks, interpreted.
"""

import importlib.util
import json
import os
import urllib.request

import numpy
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from veles_tpu.ops import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNT, WIDTH, INNER, TOP_K = 8, 128, 256, 2
CLOSE = dict(rtol=1e-4, atol=1e-5)
#: an inner width, and a chip's VMEM in MiB, at which the rule takes
#: the toy's float32 experts in slices: the streaming kernel in two,
#: the tiled one in four (at ``SLICED_ROW_TILE`` rows a tile)
WIDE, SMALL_VMEM_MIB = 1024, 14


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


@pytest.fixture(scope="module")
def wide():
    return _experts(inner=WIDE)


@pytest.fixture
def on_the_chip(monkeypatch):
    """The rule as the TPU reads it; the kernel itself still resolves
    interpret mode from the real platform."""
    monkeypatch.setattr(moe, "on_tpu", lambda: True)


def _small_vmem(monkeypatch, mib=SMALL_VMEM_MIB):
    """A chip whose VMEM holds the wide toy's experts only in slices."""
    monkeypatch.setattr(moe, "device_kind", lambda: "TPU v5 lite")
    monkeypatch.setattr(moe, "VMEM_MIB", {"TPU v5 lite": mib})


def _slices(rows, experts):
    """The slices the rule takes an expert in for ``rows``."""
    return experts["w_gate"].shape[-1] // moe.expert_plan(rows, experts)[2]


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
    assert moe.expert_plan(rows, experts)[0] == "grouped"
    grouped = moe.routed_experts(h, chosen, weights, experts, **kwargs)
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    assert moe.expert_plan(rows, experts)[0] == path
    streamed = moe.routed_experts(h, chosen, weights, experts, **kwargs)
    return grouped, streamed


@pytest.mark.parametrize("case", [
    "uneven", "not_live", "rows_8", "rows_256",
    # an expert's rows from 16 rows past a tile's start to the last row:
    # the visit's last tile is pulled back over rows its first one took
    "last_tile",
    # over STREAM_MAX_ROWS, the tiled kernel: rows that are no whole
    # number of tiles, an expert with every row and one with none,
    # ``live`` leaving tokens out, a tile that every expert shares
    "tiled_rows_602", "tiled_uneven", "tiled_not_live", "tiled_rows_1024",
    # the same with the experts in slices of their inner width
    "sliced_uneven", "sliced_not_live", "sliced_last_tile",
    "sliced_tiled_rows_602",
    "sliced_tiled_uneven", "sliced_tiled_not_live"])
def test_the_streamed_experts_agree_with_ragged_dot_and_the_loop(
        experts, wide, monkeypatch, case):
    """(a) an expert with no row and one with every row; (b) ``live``
    leaving slots out; (d) 8 and 256 rows: the same ``y`` from the
    kernel, from ``ragged_dot`` and from the loop, the same ``load``;
    with the experts whole and in slices."""
    sliced = case.startswith("sliced_")
    if sliced:
        case, experts = case[len("sliced_"):], wide
        _small_vmem(monkeypatch)
    live, path = None, "tiled" if case.startswith("tiled") else "streamed"
    if case == "uneven":
        h, chosen, weights = _tokens(1, 41, never=3, always=5)
    elif case == "not_live":
        h, chosen, weights = _tokens(2, 6)
        live = jnp.asarray([True, False, True, True, False, False])
    elif case == "last_tile":
        # the last expert takes rows 80 to 128 of the sorted 128
        h, chosen, weights = _tokens(9, 64, count=COUNT - 1)
        chosen = chosen.at[:48, 1].set(COUNT - 1)
    elif case == "tiled_uneven":
        h, chosen, weights = _tokens(7, 290, never=3, always=5)
    elif case == "tiled_not_live":
        h, chosen, weights = _tokens(8, 300)
        live = jnp.asarray(numpy.random.RandomState(8).rand(300) > 0.4)
    else:
        h, chosen, weights = _tokens(3, int(case.split("_")[-1]) // TOP_K)
    (grouped, load), (streamed, load_streamed) = _both(
        h, chosen, weights, experts, monkeypatch, path, live=live)
    assert _slices(chosen.size, experts) == (
        (4 if path == "tiled" else 2) if sliced else 1)
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


@pytest.mark.parametrize("path", ["grouped", "streamed", "tiled",
                                  "sliced_streamed", "sliced_tiled"])
def test_the_held_quarters_add_up_to_the_layer(experts, wide, monkeypatch,
                                               path):
    """(c) each quarter of the experts as a share of its own, with the
    experts whole and in slices."""
    sliced = path.startswith("sliced_")
    if sliced:
        path, experts = path[len("sliced_"):], wide
        _small_vmem(monkeypatch)
    h, chosen, weights = _tokens(4, 290 if path == "tiled" else 29)
    if path != "grouped":
        monkeypatch.setattr(moe, "on_tpu", lambda: True)
    assert (_slices(chosen.size, experts) > 1) == sliced
    whole, load = moe.routed_experts(h, chosen, weights, experts)
    total, loads = jnp.zeros_like(h), []
    for first in range(0, COUNT, COUNT // 4):
        held = jax.tree.map(lambda w: w[first:first + COUNT // 4],
                            experts)
        assert moe.expert_plan(chosen.size, held)[0] == path
        assert _slices(chosen.size, held) == _slices(chosen.size, experts)
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


@pytest.mark.parametrize("routing", ["fits", "overflows", "not_live"])
def test_a_held_share_takes_only_its_rows_where_they_fit(experts,
                                                          monkeypatch,
                                                          routing):
    """A quarter of the experts held of all of them routed: the held
    rows, ``held_rows`` of them (twice the even share, in whole tiles),
    go through the tiled kernel and come back scaled; a batch that
    sends the quarter more takes every row. Both are the loop's."""
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    h, chosen, weights = _tokens(6, 600)
    live = None
    if routing == "overflows":
        chosen = jnp.tile(jnp.arange(TOP_K, dtype=jnp.int32), (600, 1))
    elif routing == "not_live":
        live = jnp.asarray(numpy.arange(600) % 5 != 0)
    quarter = COUNT // 4
    held = jax.tree.map(lambda w: w[:quarter], experts)
    rows = moe.held_rows(chosen.size, held, COUNT)
    assert rows == 640 and moe.expert_plan(rows, held)[0] == "tiled"
    y, load = moe.routed_experts(h, chosen, weights, held,
                                 held=(0, quarter), live=live,
                                 routed=COUNT)
    assert (int(jnp.sum(load)) <= rows) == (routing != "overflows")
    numpy.testing.assert_allclose(
        numpy.asarray(y), numpy.asarray(_loop(
            h, chosen, weights, held, held=(0, quarter), live=live)),
        **CLOSE)
    # every expert held, or a share whose rows would stream: all rows
    assert moe.held_rows(chosen.size, experts, COUNT) is None
    assert moe.held_rows(200 * TOP_K, held, COUNT) is None


@pytest.mark.parametrize("path, tokens, inner", [
    ("streamed", 19, INNER), ("tiled", 270, INNER),
    ("streamed", 19, WIDE), ("tiled", 270, WIDE)])
def test_bfloat16_operands_accumulate_and_gate_in_float32(
        on_the_chip, monkeypatch, path, tokens, inner):
    """As served: bfloat16 rows and matrices; the kernel's products
    stand as close to the float32 loop as ``ragged_dot``'s do, the
    experts whole and, at the wide toy on a small chip, in slices whose
    float32 parts are summed in float32."""
    experts = _experts(inner=inner, dtype=jnp.bfloat16)
    h, chosen, weights = _tokens(5, tokens)
    h = h.astype(jnp.bfloat16)
    # (bfloat16 halves the matrices' claim: a smaller chip than the
    # float32 toy's takes the wide experts in slices, the others whole)
    _small_vmem(monkeypatch, 12)
    assert moe.expert_plan(chosen.size, experts)[0] == path
    assert (_slices(chosen.size, experts) > 1) == (inner == WIDE)
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
    assert moe.expert_plan(rows, experts)[0] == path
    # shapes alone say as much: what the decoder asks before a dispatch
    if not sharded:
        assert moe.expert_plan(rows, jax.eval_shape(
            lambda: experts))[0] == path


def _shapes(count, width, inner):
    sds = jax.ShapeDtypeStruct
    return {"w_gate": sds((count, width, inner), jnp.bfloat16),
            "w_up": sds((count, width, inner), jnp.bfloat16),
            "w_down": sds((count, inner, width), jnp.bfloat16)}


#: (count, width, inner) of the benchmark's expert models' held experts
JOYAI, LFM2, COMMAND_A = (256, 2048, 768), (32, 2048, 1792), \
    (16, 4096, 4096)


@pytest.mark.parametrize("shape, rows, kind, path, tile, slices", [
    # whole experts fit: one slice, the row tile the kernels always took
    (JOYAI, 256, "TPU v5 lite", "streamed", 32, 1),
    (JOYAI, 32768, "TPU v5 lite", "tiled", moe.ROW_TILE, 1),
    (LFM2, 256, "TPU v5 lite", "streamed", 32, 1),
    (LFM2, 16384, "TPU v5 lite", "tiled", moe.ROW_TILE, 1),
    # two whole 4096 x 4096 experts do not: a decode step of 48 slots x
    # top-8 and an admission of 8,192 tokens x top-8 take them in slices
    (COMMAND_A, 384, "TPU v5 lite", "streamed", 32, 4),
    (COMMAND_A, 65536, "TPU v5 lite", "tiled", moe.SLICED_ROW_TILE, 4),
    (COMMAND_A, 384, "TPU v5p", "streamed", 32, 16),
    # a chip whose VMEM is not known: whole, as the kernels always did
    (COMMAND_A, 384, "TPU v9", "streamed", 32, 1),
    # rows too wide for even the narrowest slice beside them
    ((16, 65536, 128), 512, "TPU v5 lite", "grouped", None, 1),
])
def test_the_rule_slices_an_expert_only_where_it_does_not_fit_whole(
        monkeypatch, shape, rows, kind, path, tile, slices):
    """The rule at the benchmark's published widths (shapes only: what
    the decoder asks before a dispatch): an expert that fits whole
    beside the rows is one slice, a 4096 x 4096 one on a v5e more; the
    tiled kernel takes the taller row tile only where it slices."""
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    monkeypatch.setattr(moe, "device_kind", lambda: kind)
    experts = _shapes(*shape)
    assert moe.expert_plan(rows, experts) == (
        path, tile, shape[2] // slices)


def _pallas_calls(fn, *args):
    """``(name, grid, block shapes, vmem_limit_bytes)`` of every
    ``pallas_call`` the traced ``fn`` holds."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                mapping = eqn.params["grid_mapping"]
                yield (eqn.params["name"], tuple(mapping.grid),
                       [tuple(b.block_shape)
                        for b in mapping.block_mappings],
                       eqn.params["compiler_params"][
                           "mosaic_tpu"].vmem_limit_bytes)
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", value)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


def _blocked(*shape):
    return tuple(pl.Blocked(n) for n in shape)


@pytest.mark.parametrize("shape, tokens, top_k, want", [
    # the programs of the parent of the slices, pinned: the decode step
    # (32 and 64 slots) and the largest part of an admission of each
    (JOYAI, 32, 8, ("moe_streamed_experts", (256,), 256, 768, 34373632)),
    (JOYAI, 4096, 8, ("moe_tiled_experts", (511,), 128, 768, 33685504)),
    (LFM2, 64, 4, ("moe_streamed_experts", (32,), 256, 1792, 59932672)),
    (LFM2, 4096, 4, ("moe_tiled_experts", (159,), 128, 1792, 60424192)),
    # the window cell's held experts: a second grid axis, the slices
    (COMMAND_A, 48, 8, ("moe_streamed_experts", (16, 4), 384, 1024,
                        79036416)),
    (COMMAND_A, 8192, 8, ("moe_tiled_experts", (271, 4), 256, 1024,
                          82837504)),
])
def test_whole_experts_lower_the_kernels_they_always_did(
        monkeypatch, shape, tokens, top_k, want):
    """On a v5e the expert models whose experts fit whole lower the
    grid, the blocks and the VMEM claim they lowered before experts
    came in slices; Command A+'s take theirs a slice a grid step."""
    monkeypatch.setattr(moe, "on_tpu", lambda: True)
    monkeypatch.setattr(moe, "device_kind", lambda: "TPU v5 lite")
    count, width, inner = shape
    sds = jax.ShapeDtypeStruct
    name, grid, rows, sliced, claim = want
    assert _pallas_calls(
        lambda h, c, w, e: moe.routed_experts(h, c, w, e),
        sds((tokens, width), jnp.bfloat16),
        sds((tokens, top_k), jnp.int32),
        sds((tokens, top_k), jnp.float32), _shapes(*shape)) == [(
            name, grid, [_blocked(rows, width),
                         _blocked(1, width, sliced),
                         _blocked(1, width, sliced),
                         _blocked(1, sliced, width),
                         _blocked(rows, width)], claim)]


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


@pytest.mark.parametrize("fault, slice_width", [
    (None, None), ("boundary", None), (None, INNER // 2),
    ("boundary", INNER // 2)])
def test_a_boundary_tiles_rows_given_to_the_neighbour_are_caught(
        experts, fault, slice_width):
    """The comparison the other tests rest on sees a tile that two
    experts share cut at the wrong row: three rows of the one computed
    by the other; with the experts whole and in two slices, whose
    second adds to the rows the first set."""
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
                            experts, tile=64, slice_width=slice_width)
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


def _toy(inner):
    """The benchmark configuration's rehearsal model with lane-wide
    expert matrices (128 x ``inner``), in float32."""
    with open(os.path.join(
            ROOT, "benchmark/configs/joyai-llm-flash.json")) as fin:
        config = json.load(fin)
    config.update(config["rehearsal"])
    config.update(hidden_size=128, moe_intermediate_size=inner)
    params, table = _reference().init_params(11, config)
    return config, jax.tree.map(lambda a: a.astype(jnp.float32),
                                (params, table))


@pytest.fixture(scope="module")
def toy_model():
    return _toy(128)


@pytest.fixture(scope="module")
def wide_toy_model():
    """The same with experts the small chip takes in slices."""
    return _toy(WIDE)


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
    assert decoder.moe_counters()["moe_expert_slices"] == {
        "1": counts["admit"] + counts["chunk"]}
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


@pytest.mark.parametrize("platform", ["cpu", "tpu", "tpu_sliced"])
def test_healthz_metrics_and_spans_say_the_path(
        request, observability, tmp_path, monkeypatch, platform):
    """Where the rule cannot take a kernel every dispatch says
    ``grouped``; with the platform steered and the threshold at this
    toy's chunk (16 rows), an admission says ``tiled`` and a chunk
    ``streamed``, in ``/healthz``, in ``/metrics`` and on the spans;
    and each says the slices its kernel took an expert in: one where
    the experts fit whole, more for wide experts on a small chip."""
    from veles_tpu.observe.trace_export import export_chrome_trace
    from veles_tpu.serving import GenerateAPI

    config, (params, table) = request.getfixturevalue(
        "wide_toy_model" if platform == "tpu_sliced" else "toy_model")
    if platform != "cpu":
        monkeypatch.setattr(moe, "on_tpu", lambda: True)
        monkeypatch.setattr(moe, "STREAM_MAX_ROWS",
                            4 * config["num_experts_per_tok"])
    if platform == "tpu_sliced":
        _small_vmem(monkeypatch)
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
        if platform != "cpu" else dict.fromkeys(
            ("decode.admit", "decode.dispatch"), "grouped")
    # (the wide toy's tiled kernel four slices at SLICED_ROW_TILE rows,
    # its streaming kernel two)
    slices = {"decode.admit": 4, "decode.dispatch": 2} \
        if platform == "tpu_sliced" else dict.fromkeys(by_span, 1)
    want = {"streamed": 0, "tiled": 0, "grouped": 0}
    want[by_span["decode.admit"]] += counts["admit"]
    want[by_span["decode.dispatch"]] += counts["chunk"]
    assert counts["admit"] and counts["chunk"]
    said = health["counters"]["moe_expert_path"]
    assert said == want
    by_slices = {}
    for span, n in (("decode.admit", counts["admit"]),
                    ("decode.dispatch", counts["chunk"])):
        by_slices[str(slices[span])] = by_slices.get(str(slices[span]),
                                                     0) + n
    assert health["counters"]["moe_expert_slices"] == by_slices
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
    assert all(e["args"]["moe_expert_slices"] == slices[e["name"]]
               for e in spans)
