"""A routed expert layer that drops no token and pads no expert.

The serving-side expert layer (``parallel/expert.py`` is the training
one: top-1, capacity-bounded, tokens over capacity dropped). Every
token goes to its ``top_k`` experts whatever the others chose:

    s = sigmoid(h . W_g)                    (float32, every expert)
    chosen = top_k(s + b)                   (b steers the choice only)
    w = s[chosen] / (sum(s[chosen]) + eps) * scale
    y = sum_i w_i . E_chosen_i(h)           E_e = W_down(silu(W_gate h) * W_up h)

The products are grouped: the (token, expert) assignments are sorted
by expert and each expert multiplies the rows that chose it, so an
expert no token chose is not read. One algorithm, three tilings, chosen
from static shapes when a program is traced (:func:`expert_path`; the
record each constant was read from: PERF.md §5, the rows sweep,
``scripts/moe_rows_sweep.py``):

- ``"streamed"`` (:func:`streamed_experts`): few rows (a decode step:
  live slots x ``top_k`` assignments over as many experts). The work
  is a read of the touched experts and nothing else, so a Pallas
  kernel visits each touched expert once and takes its three matrices
  from HBM whole, a DMA of megabytes each, while the one before is
  multiplied; the ``(rows, F)`` intermediate never leaves VMEM. Rows
  and result stay in VMEM for the whole call, which is what bounds it
  (``STREAM_MAX_ROWS``).
- ``"tiled"`` (:func:`tiled_experts`): more rows than that (a part of
  an admission: up to 128 rows an expert in the one expert model of the
  benchmark, 512 in the other). The experts leave HBM as above, once
  and whole; the sorted rows and the result pass through VMEM a tile
  of ``ROW_TILE`` rows at a time, so the call's VMEM does not grow
  with the rows. A tile that two experts' rows share is visited by
  each, under a mask. 2.2-2.6 times ahead of ``ragged_dot`` at 1,024 to
  32,768 rows on the v5e at the first model's widths, 1.8-2.3 at the
  second's.
- ``"grouped"`` (``jax.lax.ragged_dot``: the compiler's own grouped
  kernel, 512 x 256 weight tiles): every platform but the TPU (the
  plain forward in tests), widths that are not lane multiples, leaves
  sharded over a mesh, and experts whose three matrices, twice (the
  one multiplied and the next on its way), do not fit the chip's VMEM
  beside the rows (a 4096 x 4096 expert is 96 MB of them).

The layer is told which experts it holds (``held = (first, count)``):
it routes over all of them and computes the part of ``y`` that the
held ones give; the parts of disjoint shares add up to the whole
layer.

The named scopes (``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.combine``, ``moe.shared``) are HLO metadata that the scope table
(``observe/xla_stats.scope_table``) carries to a traced op.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.platform import (VMEM_MIB, device_kind, on_tpu,
                                    pallas_interpret)

#: the most assignments (rows: tokens x ``top_k``) a call may have and
#: still keep rows and result resident in VMEM (the streaming kernel);
#: above it they pass through a tile at a time (the tiled kernel). Not
#: a crossover: on the v5e at the published widths of both expert
#: models of the benchmark (256 experts of 2048 x 768 and 32 of 2048 x
#: 1792, bfloat16) the two kernels are within 5% of each other at 256
#: and 512 rows and either is 1.8-2.6 times ahead of ``ragged_dot``
#: (PERF.md §5, the rows sweep of PR 35), so the decode step's program
#: stays what PR 30 made it.
STREAM_MAX_ROWS = 512
#: rows of one product inside the kernel. An expert's rows start
#: anywhere, a tile at a multiple of 16 (a bfloat16 sublane tile), so
#: a tile of 32 takes any expert of up to 17 rows in one pass.
_ROW_TILE, _ROW_ALIGN = 32, 16
#: rows of one tile of the tiled kernel: what a grid step brings into
#: VMEM and multiplies at once. The MXU's own height; on the v5e tiles
#: of 256 rows were no faster at any size at either model's widths (a
#: product of 256 rows runs nearer the MXU's peak, and an expert's
#: rows reach as many tiles of 256 as of 128: the same sweep). With
#: it the tiled kernel is 2.2-2.6 times ahead of ``ragged_dot`` from
#: 1,024 to 32,768 rows at the first model's widths (128 rows an
#: expert at the most) and 1.8-2.3 times from 1,024 to 16,384 at the
#: second's (512 rows an expert, where it runs at 113 TFLOP/s): no
#: number of rows an expert was found at which ``ragged_dot`` leads,
#: so the rule has no such threshold.
ROW_TILE = 128


def route(h, router, bias, top_k, scale, eps=0.0):
    """``(chosen (N, top_k) int32, weights (N, top_k) float32)`` for
    tokens ``h`` (N, E): scores, bias add and top-k in float32 at full
    precision (a bfloat16 score ties where a float32 one does not).
    ``eps`` (static) is what a model adds to the normalising sum;
    ``bias`` None: a model whose choice takes no bias."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(
            scores if bias is None else scores + bias.astype(jnp.float32),
            top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        total = jnp.sum(picked, -1, keepdims=True)
        weights = picked / (total + eps if eps else total) * scale
    return chosen.astype(jnp.int32), weights


def swiglu(h, p):
    """``W_down(silu(W_gate h) * W_up h)``: the dense feed-forward and
    the shared expert."""
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def _sharded(leaf):
    """Whether ``leaf`` says it lies over more than one device: an
    array by its sharding, a tracer by the mesh its type carries. (A
    jit that shards its arguments without saying so in their types
    shows nothing here; no serving path shards the experts yet.)"""
    sharding = getattr(leaf, "sharding", None)
    if isinstance(leaf, jax.core.Tracer) or sharding is None:
        sharding = getattr(jax.typeof(leaf), "sharding", None)
        return sharding is not None and not sharding.mesh.empty \
            and any(axis is not None for axis in sharding.spec)
    return len(sharding.device_set) > 1


def expert_path(n_rows, experts):
    """Which tiling the grouped products of ``n_rows`` assignments
    over the stacked ``experts`` take: ``"streamed"``, ``"tiled"`` or
    ``"grouped"``. Read off the platform and static shapes, so it is
    known when a program is traced (and to whoever knows the program's
    shapes: the decoder books it per dispatch)."""
    w_gate = experts["w_gate"]
    _, width, inner = w_gate.shape
    if on_tpu() and width % 128 == 0 and inner % 128 == 0 \
            and not _sharded(w_gate) and _fits(n_rows, experts):
        return "streamed" if n_rows <= STREAM_MAX_ROWS else "tiled"
    return "grouped"


def _fits(n_rows, experts):
    """Whether the kernel that ``n_rows`` assignments take claims no
    more VMEM than the chip has (a chip whose VMEM is not known: as
    the kernels always did)."""
    mib = VMEM_MIB.get(device_kind())
    if mib is None:
        return True
    rows = jax.ShapeDtypeStruct((0,), experts["w_gate"].dtype)
    if n_rows <= STREAM_MAX_ROWS:
        claim = _vmem_claim(rows, -(-n_rows // _ROW_TILE) * _ROW_TILE,
                            experts, _ROW_TILE)
    else:
        claim = _vmem_claim(rows, ROW_TILE, experts, ROW_TILE)
    return claim <= mib << 20


def _whole_tiles(source, tile):
    """``source`` (the sorted rows' tokens) with zeros behind it to a
    whole number of tiles; nothing visits the rows added."""
    return jnp.pad(source, (0, -source.shape[0] % tile))


def _vmem_claim(rows, held, experts, tile):
    """``vmem_limit_bytes`` of a kernel that keeps ``held`` of ``rows``
    and as many of its float32 result in VMEM beside one expert's
    matrices: two buffers of everything the pipeline moves, the
    products' float32 temporaries of one tile, and room for the
    compiler's own."""
    _, width, inner = experts["w_gate"].shape
    return 2 * (3 * width * inner * experts["w_gate"].dtype.itemsize
                + held * width * (rows.dtype.itemsize + 4)) \
        + 4 * tile * (3 * inner + 2 * width) + (8 << 20)


def visit_table(load, n_rows):
    """The streaming kernel's walk over ``load`` (count,), the rows
    each expert got: ``(expert, first row, rows)`` of each visit, int32
    ``(visits,)`` each with ``visits = min(count, n_rows)``. The
    touched experts come first, in their order; a visit past the last
    of them repeats its expert (the same block index: no DMA) and has
    no rows."""
    count = load.shape[0]
    visit = jnp.arange(min(count, n_rows))
    touched = load > 0
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    ids = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    ids = jnp.take(ids, jnp.minimum(visit, jnp.maximum(n_touched - 1, 0)))
    first = jnp.cumsum(load) - load
    return (ids, jnp.take(first, ids),
            jnp.where(visit < n_touched, jnp.take(load, ids), 0))


def _stream_kernel(ids_ref, first_ref, n_ref, rows_ref, gate_ref, up_ref,
                   down_ref, out_ref):
    """One visit: the expert's matrices are in VMEM whole (the next
    visit's are on their way); its rows ``[first, first + n)`` of the
    resident ``rows`` go through gate, up and down a tile at a time
    and land in the resident ``out``, the rows of other experts that
    a tile covers left as they were."""
    visit = pl.program_id(0)
    n_rows = rows_ref.shape[0]

    @pl.when(visit == 0)
    def _clear():
        out_ref[...] = jnp.zeros_like(out_ref)

    first, n = first_ref[visit], n_ref[visit]

    @pl.when(n > 0)
    def _products():
        base = first // _ROW_ALIGN * _ROW_ALIGN

        def tile(i, carry):
            at = pl.multiple_of(jnp.minimum(base + i * _ROW_TILE,
                                            n_rows - _ROW_TILE),
                                _ROW_ALIGN)
            x = rows_ref[pl.ds(at, _ROW_TILE), :]
            gate = jnp.dot(x, gate_ref[0],
                           preferred_element_type=jnp.float32)
            up = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
            inner = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
            y = jnp.dot(inner, down_ref[0],
                        preferred_element_type=jnp.float32)
            row = at + lax.broadcasted_iota(jnp.int32, (_ROW_TILE, 1), 0)
            mine = (row >= first) & (row < first + n)
            out_ref[pl.ds(at, _ROW_TILE), :] = jnp.where(
                mine, y, out_ref[pl.ds(at, _ROW_TILE), :])
            return carry

        lax.fori_loop(0, (first + n - base + _ROW_TILE - 1) // _ROW_TILE,
                      tile, 0)


def streamed_experts(rows, visits, experts, interpret=None):
    """``W_down(silu(W_gate r) * W_up r)`` of every row ``r`` of
    ``rows`` (M, E), sorted by expert, M a multiple of the row tile,
    through the expert that :func:`visit_table`'s ``visits`` give it:
    ``(M, E)`` float32, zero where no visit reaches. Each touched
    expert's ``w_gate``/``w_up`` (E, F) and ``w_down`` (F, E) leave
    HBM once, whole, from the stacked leaves as they lie; ``rows`` and
    the result stay in VMEM throughout. Operands as they come
    (bfloat16 in serving), products accumulated and gated in float32.
    ``interpret=None`` resolves from the platform."""
    if interpret is None:
        interpret = pallas_interpret()
    n_rows, width = rows.shape
    inner = experts["w_gate"].shape[-1]
    assert n_rows % _ROW_TILE == 0, (n_rows, _ROW_TILE)

    def resident(v, *_):
        return (0, 0)

    def visited(v, ids, *_):
        return (ids[v], 0, 0)

    return pl.pallas_call(
        _stream_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=visits[0].shape,
            in_specs=[pl.BlockSpec((n_rows, width), resident),
                      pl.BlockSpec((1, width, inner), visited),
                      pl.BlockSpec((1, width, inner), visited),
                      pl.BlockSpec((1, inner, width), visited)],
            out_specs=pl.BlockSpec((n_rows, width), resident)),
        out_shape=jax.ShapeDtypeStruct((n_rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_claim(rows, n_rows, experts, _ROW_TILE)),
        name="moe_streamed_experts",
        interpret=interpret,
    )(*visits, rows, experts["w_gate"], experts["w_up"],
      experts["w_down"])


def tile_table(load, n_rows, tile=ROW_TILE):
    """The tiled kernel's walk over ``load`` (count,), the rows each
    expert got of ``n_rows`` sorted ones: ``(expert, tile, first row,
    end row)`` of each item, int32 ``(items,)`` each. An item is one
    expert over one tile of ``tile`` rows: a touched expert has one for
    every tile its rows ``[first, end)`` reach, in the rows' order, so
    consecutive items keep the expert (no new matrices to fetch), or
    the tile (the result stays in VMEM), or neither. ``items`` is the
    most there can be, ``tiles + min(count, n_rows) - 1``; an item past
    the last repeats its expert and tile and has no rows."""
    count = load.shape[0]
    items = -(-n_rows // tile) + min(count, n_rows) - 1
    end = jnp.cumsum(load)
    first = end - load
    reach = jnp.where(load > 0, (end - 1) // tile - first // tile + 1, 0)
    stop = jnp.cumsum(reach)
    at = jnp.arange(items, dtype=jnp.int32)
    item = jnp.minimum(at, jnp.maximum(stop[-1] - 1, 0))
    ids = jnp.minimum(jnp.searchsorted(stop, item, side="right"),
                      count - 1).astype(jnp.int32)
    tiles = jnp.take(first // tile + reach - stop, ids) + item
    live = at < stop[-1]
    return (ids, tiles, jnp.where(live, jnp.take(first, ids), 0),
            jnp.where(live, jnp.take(end, ids), 0))


def _tile_kernel(ids_ref, tiles_ref, first_ref, end_ref, rows_ref, gate_ref,
                 up_ref, down_ref, out_ref):
    """One item: the expert's matrices are in VMEM whole (the next
    expert's on their way) and so is one tile of the rows; the rows of
    the tile that are the expert's, ``[first, end)``, take its
    products, the others keep what an earlier item of the tile gave
    them (the result's tile stays in VMEM until the walk leaves it)."""
    item = pl.program_id(0)
    tile = out_ref.shape[0]
    first, end = first_ref[item], end_ref[item]

    @pl.when(end > first)
    def _products():
        at = tiles_ref[item]
        x = rows_ref[...]
        gate = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, up_ref[0], preferred_element_type=jnp.float32)
        inner = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
        y = jnp.dot(inner, down_ref[0], preferred_element_type=jnp.float32)
        row = at * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        mine = (row >= first) & (row < end)
        # the first item of a tile finds nothing there: it writes every
        # row
        fresh = (item == 0) | (tiles_ref[jnp.maximum(item - 1, 0)] != at)

        @pl.when(fresh)
        def _first():
            out_ref[...] = jnp.where(mine, y, 0.0)

        @pl.when(jnp.logical_not(fresh))
        def _later():
            out_ref[...] = jnp.where(mine, y, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _tiled(table, rows, experts, tile, interpret):
    """The ``pallas_call`` over :func:`tile_table`'s ``table``. A
    function jitted on its own, so that a program with an expert layer
    in every block lowers the kernel once and calls it (lowered once a
    layer it costs each of an expert model's admit programs seconds of
    every set-up: PERF.md §6, PR 32)."""
    n_rows, width = rows.shape
    inner = experts["w_gate"].shape[-1]
    assert n_rows % tile == 0, (n_rows, tile)

    def tiled(i, ids, tiles, *_):
        return (tiles[i], 0)

    def visited(i, ids, *_):
        return (ids[i], 0, 0)

    return pl.pallas_call(
        _tile_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=table[0].shape,
            in_specs=[pl.BlockSpec((tile, width), tiled),
                      pl.BlockSpec((1, width, inner), visited),
                      pl.BlockSpec((1, width, inner), visited),
                      pl.BlockSpec((1, inner, width), visited)],
            out_specs=pl.BlockSpec((tile, width), tiled)),
        out_shape=jax.ShapeDtypeStruct((n_rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_claim(rows, tile, experts, tile)),
        name="moe_tiled_experts",
        interpret=interpret,
    )(*table, rows, experts["w_gate"], experts["w_up"], experts["w_down"])


def tiled_experts(rows, table, experts, tile=ROW_TILE, interpret=None):
    """:func:`streamed_experts`' products for any number of rows:
    ``rows`` (M, E), sorted by expert, M whole tiles of ``tile`` rows
    (a multiple of 16, as the table was made for), each row through
    the expert whose items in :func:`tile_table`'s ``table`` cover it:
    ``(M, E)`` float32; a tile that no item reaches holds whatever was
    there. Each touched expert's matrices leave HBM once, whole, as in
    the streaming kernel; the rows and the result pass through VMEM a
    tile at a time and the ``(tile, F)`` gated intermediate never
    leaves it, so the call's VMEM is the same at any M. Operands as
    they come, products accumulated and gated in float32.
    ``interpret=None`` resolves from the platform."""
    if interpret is None:
        interpret = pallas_interpret()
    return _tiled(table, rows, experts, tile=tile, interpret=interpret)


def grouped_experts(rows, load, experts):
    """The same products through ``jax.lax.ragged_dot``, ``load`` (count,)
    the rows of each expert in turn: ``(M, E)`` in the rows' type."""
    inner = jax.nn.silu(lax.ragged_dot(rows, experts["w_gate"], load)) \
        * lax.ragged_dot(rows, experts["w_up"], load)
    return lax.ragged_dot(inner, experts["w_down"], load)


def routed_experts(h, chosen, weights, experts, held=None, live=None):
    """The held experts' part of the layer for tokens ``h`` (N, E):
    ``(y (N, E), load (n_held,) int32)``. ``experts`` holds the
    stacked ``w_gate``/``w_up`` (count, E, F) and ``w_down``
    (count, F, E) of experts ``first .. first + count``; ``live``
    (N,) bool leaves a token out altogether (a padded position, an
    idle slot: it reads no expert and counts in no load). ``load`` is
    the assignments each held expert got."""
    count = experts["w_gate"].shape[0]
    first = 0 if held is None else held[0]
    n, top_k = chosen.shape
    path = expert_path(n * top_k, experts)
    with jax.named_scope("moe.dispatch"):
        local = chosen - first
        mine = (local >= 0) & (local < count)
        if live is not None:
            mine &= live[:, None]
        # an assignment that is not computed here sorts behind every
        # expert's rows, where no group reaches
        key = jnp.where(mine, local, count).reshape(-1)
        order = jnp.argsort(key)
        back = jnp.argsort(order)
        # (a comparison and a sum: a scatter-add is slow on the TPU)
        load = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                       dtype=jnp.int32)
        source = order // top_k
        if path == "streamed":
            source = _whole_tiles(source, _ROW_TILE)
            visits = visit_table(load, n * top_k)
        elif path == "tiled":
            source = _whole_tiles(source, ROW_TILE)
            table = tile_table(load, source.shape[0])
        rows = jnp.take(h, source, axis=0)
    with jax.named_scope("moe.experts"):
        if path == "streamed":
            out = streamed_experts(rows, visits, experts)
        elif path == "tiled":
            out = tiled_experts(rows, table, experts)
        else:
            out = grouped_experts(rows, load, experts)
    with jax.named_scope("moe.combine"):
        # back in the tokens' order; a row past the last group holds
        # whatever the grouped product left there: selected away, not
        # multiplied by 0
        share = jnp.where(mine, weights, 0.0)
        out = jnp.take(out, back, axis=0).reshape(n, top_k, -1)
        y = jnp.sum(jnp.where(mine[..., None],
                              out.astype(jnp.float32) * share[..., None],
                              0.0), axis=1)
    return y.astype(h.dtype), load


def expert_layer(h, p, top_k, scale, held=None, live=None, eps=0.0,
                 shared_scale=1.0):
    """The whole layer for tokens ``h`` (N, E): the held experts' part
    plus the shared experts, where the layer has them (a ``shared``
    leaf: one SwiGLU, or several side by side as one of their summed
    width, whose output is their sum), that sum times ``shared_scale``
    (``1 / n`` where a model averages ``n``). A layer without a
    ``router_bias`` leaf chooses by the scores alone. Returns ``(y,
    load)``."""
    chosen, weights = route(h, p["router"], p.get("router_bias"), top_k,
                            scale, eps)
    y, load = routed_experts(h, chosen, weights, p["experts"], held, live)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            shared = swiglu(h, p["shared"])
            if shared_scale != 1.0:
                shared = shared * jnp.asarray(shared_scale, shared.dtype)
            y = y + shared
    return y, load
