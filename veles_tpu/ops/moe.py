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
from static shapes when a program is traced (:func:`expert_plan`; the
record each constant was read from: PERF.md §5, the rows sweep,
``scripts/moe_rows_sweep.py``):

- ``"streamed"`` (:func:`streamed_experts`): few rows (a decode step:
  live slots x ``top_k`` assignments over as many experts). The work
  is a read of the touched experts and nothing else, so a Pallas
  kernel visits each touched expert once and takes its three matrices
  from HBM, a DMA of megabytes each, while the one before is
  multiplied; the ``(rows, F)`` intermediate never leaves VMEM. Rows
  and result stay in VMEM for the whole call, which is what bounds it
  (``STREAM_MAX_ROWS``).
- ``"tiled"`` (:func:`tiled_experts`): more rows than that (a part of
  an admission). The sorted rows and the result pass through VMEM a
  tile of rows at a time, so the call's VMEM does not grow with the
  rows; each item of the walk is one expert over one tile, and a tile
  that two experts' rows share is visited by each, under a mask.
  2.2-2.6 times ahead of ``ragged_dot`` at 1,024 to 32,768 rows on the
  v5e at the widths of the benchmark's first expert model, 1.8-2.3 at
  the second's.
- ``"grouped"`` (``jax.lax.ragged_dot``: the compiler's own grouped
  kernel, 512 x 256 weight tiles): every platform but the TPU (the
  plain forward in tests), widths that are not lane multiples, and
  leaves sharded over a mesh.

**Slices of the inner width.** Both kernels pipeline what they take
from HBM, so an expert's matrices are in VMEM twice (the one
multiplied and the next on its way). Where an expert is small (2048 x
768, 2048 x 1792) they come whole: one grid step an expert (or an
item), the program it always was. Where two whole experts do not fit
beside the rows (4096 x 4096: 201 MB of bfloat16 against the v5e's
128 MiB) a grid step takes a slice ``f`` of the inner width F --
``w_gate[e, :, f0:f1]``, ``w_up[e, :, f0:f1]``, ``w_down[e, f0:f1, :]``
-- and adds ``silu(x Wg_s) * (x Wu_s) . Wd_s`` into the float32 result,
which stays in VMEM across an expert's (an item's) slices, the slice
the innermost grid axis. The slice is the widest of F, F/2, F/4, ...
(a multiple of 128) whose claim (:func:`_vmem_claim`) fits 25/32 of the
chip's VMEM (:func:`widest_slice`); the mathematics is the same sum over
F taken in parts, each in float32. The gate and up slices are strided
blocks (rows of ``2 f`` bytes at a stride of ``2 F``), read as they
lie: no leaf is laid out anew at load (which set-up would pay) and no
second form of the kernels slices the contraction instead. On the v5e
at 4096 x 4096 the streamed kernel reads such slices at 700-718 GB/s,
the narrowest (1 KB rows) no slower than the widest, within 8% of a
bare block read of the leaves (``scripts/moe_rows_sweep.py
--slice-widths``, PERF.md §5): the stride is not what bounds it.
The streamed kernel still reads each touched expert once; the tiled
kernel reads an expert's slices once an item, so where it slices it
takes ``SLICED_ROW_TILE`` rows a tile, enough for an item to be bound
by its products rather than by the read.

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
#: a crossover: on the v5e at the published widths of the benchmark's
#: first two expert models (256 experts of 2048 x 768 and 32 of 2048 x
#: 1792, bfloat16) the two kernels are within 5% of each other at 256
#: and 512 rows and either is 1.8-2.6 times ahead of ``ragged_dot``
#: (PERF.md §5, the rows sweep of PR 35), so the decode step's program
#: stays what PR 30 made it.
#: A decode step of 48 slots x top-8 (384 rows) over 4096 x 4096
#: experts streams them in four slices, 1.36 times ahead of
#: ``ragged_dot`` on the v5e.
STREAM_MAX_ROWS = 512
#: rows of one product inside the streaming kernel. An expert's rows
#: start anywhere, a tile at a multiple of 16 (a bfloat16 sublane
#: tile), so a tile of 32 takes any expert of up to 17 rows in one
#: pass.
_ROW_TILE, _ROW_ALIGN = 32, 16
#: rows of one tile of the tiled kernel where an expert comes whole:
#: what a grid step brings into VMEM and multiplies at once. The MXU's
#: own height; on the v5e tiles of 256 rows were no faster at any size
#: at the first two expert models' widths (a product of 256 rows runs
#: nearer the MXU's peak, and an expert's rows reach as many tiles of
#: 256 as of 128: the same sweep). With it the tiled kernel is 2.2-2.6
#: times ahead of ``ragged_dot`` from 1,024 to 32,768 rows at the first
#: model's widths (128 rows an expert at the most) and 1.8-2.3 times
#: from 1,024 to 16,384 at the second's (512 rows an expert, where it
#: runs at 113 TFLOP/s): no number of rows an expert was found at which
#: ``ragged_dot`` leads, so the rule has no such threshold.
ROW_TILE = 128
#: rows of one tile of the tiled kernel where an expert comes in slices
#: (:func:`widest_slice`). Each item then reads its expert's slices
#: again, so a tile is as tall as it takes for an item's products to
#: outlast the read: at 4096 x 4096 an item's 100 MB take ~130 us at
#: the HBM's rate and 256 rows' products ~130 us at the MXU's peak (the
#: v5e's ridge is ~240 rows). On the v5e at those widths tiles of 256
#: rows are 1.86, 1.49 and 1.33 times ahead of ``ragged_dot`` at 2,048,
#: 8,192 and 65,536 rows, and ahead of tiles of 512 at each (1.84, 1.39,
#: 1.12 times: an expert's rows fill less of a taller tile; PERF.md §5).
SLICED_ROW_TILE = 256


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


def expert_plan(n_rows, experts):
    """``(path, tile, slice_width)``: the tiling that the grouped
    products of ``n_rows`` assignments over the stacked ``experts``
    take, the rows its sorted rows are padded to a whole number of
    (None for ``"grouped"``), and the slice of the inner width F that
    one grid step takes (F: the expert whole). Read off the platform
    and static shapes, so it is known when a program is traced (and to
    whoever knows the program's shapes: the decoder books it per
    dispatch). A kernel is taken where some slice of an expert fits
    the chip's VMEM beside the rows; the tiled kernel takes
    ``ROW_TILE`` rows a tile where the expert comes whole and
    ``SLICED_ROW_TILE`` where it comes in slices."""
    w_gate = experts["w_gate"]
    _, width, inner = w_gate.shape
    if on_tpu() and width % 128 == 0 and inner % 128 == 0 \
            and not _sharded(w_gate):
        if n_rows <= STREAM_MAX_ROWS:
            held = -(-n_rows // _ROW_TILE) * _ROW_TILE
            sliced = widest_slice(held, experts, _ROW_TILE)
            if sliced:
                return "streamed", _ROW_TILE, sliced
        elif widest_slice(ROW_TILE, experts, ROW_TILE) == inner:
            return "tiled", ROW_TILE, inner
        else:
            sliced = widest_slice(SLICED_ROW_TILE, experts,
                                  SLICED_ROW_TILE)
            if sliced:
                return "tiled", SLICED_ROW_TILE, sliced
    return "grouped", None, inner


def widest_slice(held, experts, tile):
    """The widest slice of the experts' inner width F -- F, F/2, F/4,
    ... while a multiple of 128 -- with which a kernel that keeps
    ``held`` rows and as much of its float32 result in VMEM, ``tile``
    rows a product, claims no more than 25/32 of the chip's VMEM (100
    of a v5e's 128 MiB: the share ``ops/slab_attention.vmem_claim``
    takes, and for its reason, the rest is what XLA prefetches the
    step's other weights through); F on a chip whose VMEM is not known
    (as the kernels always did); None where not even the narrowest
    fits."""
    inner = experts["w_gate"].shape[-1]
    mib = VMEM_MIB.get(device_kind())
    if mib is None:
        return inner
    sliced = inner
    while sliced % 128 == 0:
        if _vmem_claim(experts["w_gate"].dtype, held, experts, tile,
                       sliced) <= (mib << 20) * 25 // 32:
            return sliced
        sliced //= 2
    return None


def _whole_tiles(source, tile):
    """``source`` (the sorted rows' tokens) with zeros behind it to a
    whole number of tiles; nothing visits the rows added."""
    return jnp.pad(source, (0, -source.shape[0] % tile))


def _vmem_claim(dtype, held, experts, tile, sliced):
    """``vmem_limit_bytes`` of a kernel that keeps ``held`` rows of
    type ``dtype`` and as many of its float32 result in VMEM beside one
    expert's matrices in slices of ``sliced`` of their inner width: two
    buffers of everything the pipeline moves, the products' float32
    temporaries of one tile, and room for the compiler's own."""
    width = experts["w_gate"].shape[1]
    return 2 * (3 * width * sliced * experts["w_gate"].dtype.itemsize
                + held * width * (jnp.dtype(dtype).itemsize + 4)) \
        + 4 * tile * (3 * sliced + 2 * width) + (8 << 20)


def _weight_specs(experts, sliced, live):
    """The block specs of ``w_gate``, ``w_up`` and ``w_down`` for a
    grid whose first axis walks a table of experts (``ids``, the first
    prefetched table) and, where ``sliced`` is less than the inner
    width, whose second walks the slices: whole matrices, or a slice of
    ``sliced`` of the inner width a step. A step that ``live`` (``(step,
    *tables)`` -> bool) says has no rows keeps the last slice, which is
    the block the step before it fetched: no DMA."""
    _, width, inner = experts["w_gate"].shape
    if sliced == inner:
        def visited(i, ids, *_):
            return (ids[i], 0, 0)

        return [pl.BlockSpec((1, width, inner), visited)] * 2 \
            + [pl.BlockSpec((1, inner, width), visited)]
    last = inner // sliced - 1

    def part(i, f, *tables):
        return jnp.where(live(i, *tables), f, last)

    def across(i, f, ids, *tables):
        return (ids[i], 0, part(i, f, ids, *tables))

    def down(i, f, ids, *tables):
        return (ids[i], part(i, f, ids, *tables), 0)

    return [pl.BlockSpec((1, width, sliced), across)] * 2 \
        + [pl.BlockSpec((1, sliced, width), down)]


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
                   down_ref, out_ref, sliced=False):
    """One visit (or, ``sliced``, one slice of one visit's expert):
    the expert's matrices are in VMEM (the next visit's or slice's are
    on their way); its rows ``[first, first + n)`` of the resident
    ``rows`` go through gate, up and down a tile at a time and land in
    the resident ``out`` (added to it, ``sliced``, each row once), the
    rows of other experts that a tile covers left as they were."""
    visit = pl.program_id(0)
    n_rows = rows_ref.shape[0]
    start = visit == 0
    if sliced:
        start &= pl.program_id(1) == 0

    @pl.when(start)
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
            if sliced:
                # the last tile, pulled back to end at the last row,
                # covers rows the tile before it took: add them once
                mine &= row >= base + i * _ROW_TILE
            held = out_ref[pl.ds(at, _ROW_TILE), :]
            out_ref[pl.ds(at, _ROW_TILE), :] = jnp.where(
                mine, held + y if sliced else y, held)
            return carry

        lax.fori_loop(0, (first + n - base + _ROW_TILE - 1) // _ROW_TILE,
                      tile, 0)


def streamed_experts(rows, visits, experts, slice_width=None,
                     interpret=None):
    """``W_down(silu(W_gate r) * W_up r)`` of every row ``r`` of
    ``rows`` (M, E), sorted by expert, M a multiple of the row tile,
    through the expert that :func:`visit_table`'s ``visits`` give it:
    ``(M, E)`` float32, zero where no visit reaches. Each touched
    expert's ``w_gate``/``w_up`` (E, F) and ``w_down`` (F, E) leave
    HBM once, from the stacked leaves as they lie, whole or in slices
    of ``slice_width`` of F (None: whole); ``rows`` and the result stay
    in VMEM throughout. Operands as they come (bfloat16 in serving),
    products accumulated and gated in float32. ``interpret=None``
    resolves from the platform."""
    if interpret is None:
        interpret = pallas_interpret()
    inner = experts["w_gate"].shape[-1]
    if slice_width is None or slice_width == inner:
        return _stream_call(visits, rows, experts, inner, interpret)
    return _streamed(visits, rows, experts, slice_width=slice_width,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("slice_width", "interpret"))
def _streamed(visits, rows, experts, slice_width, interpret):
    """The sliced streaming kernel in a jit of its own, as
    :func:`_tiled`, so that a program with an expert layer in every
    block lowers it once. The experts' scope is named again inside, so
    that a traced op's innermost scopes are the layer's and not the
    jit's (``benchmark/harness/moe_scopes.py`` reads them)."""
    with jax.named_scope("moe.experts"):
        return _stream_call(visits, rows, experts, slice_width, interpret)


def _stream_call(visits, rows, experts, sliced, interpret):
    """The streaming kernel's ``pallas_call``: one grid step a visit,
    or a visit's slices, the slice the inner axis."""
    n_rows, width = rows.shape
    inner = experts["w_gate"].shape[-1]
    assert n_rows % _ROW_TILE == 0, (n_rows, _ROW_TILE)
    assert inner % sliced == 0, (inner, sliced)
    grid = visits[0].shape
    kernel = _stream_kernel
    if sliced != inner:
        grid += (inner // sliced,)
        kernel = functools.partial(_stream_kernel, sliced=True)

    def resident(v, *_):
        return (0, 0)

    def live(v, ids, first, n):
        return n[v] > 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[pl.BlockSpec((n_rows, width), resident)]
            + _weight_specs(experts, sliced, live),
            out_specs=pl.BlockSpec((n_rows, width), resident)),
        out_shape=jax.ShapeDtypeStruct((n_rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_vmem_claim(rows.dtype, n_rows, experts,
                                         _ROW_TILE, sliced)),
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
                 up_ref, down_ref, out_ref, sliced=False):
    """One item (or, ``sliced``, one slice of one item's expert): the
    expert's matrices are in VMEM (the next item's or slice's on their
    way) and so is one tile of the rows; the rows of the tile that are
    the expert's, ``[first, end)``, take its products (its first
    slice's; a later slice's are added to them), the others keep what
    an earlier item of the tile gave them (the result's tile stays in
    VMEM until the walk leaves it)."""
    item = pl.program_id(0)
    tile = out_ref.shape[0]
    first, end = first_ref[item], end_ref[item]
    if sliced:
        later = pl.program_id(1) > 0

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
        if sliced:
            @pl.when(later)
            def _add():
                out_ref[...] = jnp.where(mine, out_ref[...] + y,
                                         out_ref[...])

            carried = jnp.logical_not(fresh | later)
            fresh &= jnp.logical_not(later)

        @pl.when(fresh)
        def _first():
            out_ref[...] = jnp.where(mine, y, 0.0)

        @pl.when(carried if sliced else jnp.logical_not(fresh))
        def _later():
            out_ref[...] = jnp.where(mine, y, out_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("tile", "slice_width", "interpret"))
def _tiled(table, rows, experts, tile, slice_width, interpret):
    """The ``pallas_call`` over :func:`tile_table`'s ``table``: one grid
    step an item, or an item's slices of ``slice_width``, the slice the
    inner axis. A function jitted on its own, so that a program with an
    expert layer in every block lowers the kernel once and calls it
    (lowered once a layer it costs each of an expert model's admit
    programs seconds of every set-up: PERF.md §6). The experts' scope
    is named again inside, as :func:`_streamed` names it."""
    n_rows, width = rows.shape
    inner = experts["w_gate"].shape[-1]
    assert n_rows % tile == 0, (n_rows, tile)
    assert inner % slice_width == 0, (inner, slice_width)
    grid = table[0].shape
    kernel = _tile_kernel
    if slice_width == inner:
        def tiled(i, ids, tiles, *_):
            return (tiles[i], 0)
    else:
        grid += (inner // slice_width,)
        kernel = functools.partial(_tile_kernel, sliced=True)

        def tiled(i, f, ids, tiles, *_):
            return (tiles[i], 0)

    def live(i, ids, tiles, first, end):
        return end[i] > first[i]

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[pl.BlockSpec((tile, width), tiled)]
            + _weight_specs(experts, slice_width, live),
            out_specs=pl.BlockSpec((tile, width), tiled)),
        out_shape=jax.ShapeDtypeStruct((n_rows, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_vmem_claim(rows.dtype, tile, experts, tile,
                                         slice_width)),
        name="moe_tiled_experts",
        interpret=interpret,
    )
    with jax.named_scope("moe.experts"):
        return call(*table, rows, experts["w_gate"], experts["w_up"],
                    experts["w_down"])


def tiled_experts(rows, table, experts, tile=ROW_TILE, slice_width=None,
                  interpret=None):
    """:func:`streamed_experts`' products for any number of rows:
    ``rows`` (M, E), sorted by expert, M whole tiles of ``tile`` rows
    (a multiple of 16, as the table was made for), each row through
    the expert whose items in :func:`tile_table`'s ``table`` cover it:
    ``(M, E)`` float32; a tile that no item reaches holds whatever was
    there. An expert's matrices come whole (``slice_width`` None), once
    for its items in a row, or in slices of ``slice_width`` of F, once
    an item; the rows and the result pass through VMEM a tile at a time
    and the ``(tile, F)`` gated intermediate never leaves it, so the
    call's VMEM is the same at any M. Operands as they come, products
    accumulated and gated in float32. ``interpret=None`` resolves from
    the platform."""
    if interpret is None:
        interpret = pallas_interpret()
    return _tiled(table, rows, experts, tile=tile,
                  slice_width=slice_width or experts["w_gate"].shape[-1],
                  interpret=interpret)


def grouped_experts(rows, load, experts):
    """The same products through ``jax.lax.ragged_dot``, ``load`` (count,)
    the rows of each expert in turn: ``(M, E)`` in the rows' type."""
    inner = jax.nn.silu(lax.ragged_dot(rows, experts["w_gate"], load)) \
        * lax.ragged_dot(rows, experts["w_up"], load)
    return lax.ragged_dot(inner, experts["w_down"], load)


#: the rows a held share's products take, as a multiple of its even
#: share of the assignments (``held_rows``): a batch that routes more
#: than this to the experts held here takes every row instead
HELD_HEADROOM = 2


def held_rows(n_rows, experts, routed):
    """The rows that the products of a chip's held share of ``routed``
    experts take for ``n_rows`` assignments: ``HELD_HEADROOM`` times the
    share that even routing sends to them, in whole tiles of the tiled
    kernel, or None where that is no fewer than ``n_rows`` or where
    either number of rows would not take the tiled kernel (a decode
    step's few rows stream as they did)."""
    count = experts["w_gate"].shape[0]
    if routed is None or count >= routed:
        return None
    path, tile, _ = expert_plan(n_rows, experts)
    if path != "tiled":
        return None
    rows = -(-HELD_HEADROOM * n_rows * count // routed // tile) * tile
    if rows >= n_rows or expert_plan(rows, experts)[:2] != (path, tile):
        return None
    return rows


def routed_experts(h, chosen, weights, experts, held=None, live=None,
                   routed=None):
    """The held experts' part of the layer for tokens ``h`` (N, E):
    ``(y (N, E), load (n_held,) int32)``. ``experts`` holds the
    stacked ``w_gate``/``w_up`` (count, E, F) and ``w_down``
    (count, F, E) of experts ``first .. first + count``; ``live``
    (N,) bool leaves a token out altogether (a padded position, an
    idle slot: it reads no expert and counts in no load). ``load`` is
    the assignments each held expert got.

    Where the chip holds a share of ``routed`` experts (held ``count <
    routed``) and :func:`held_rows` gives fewer rows than there are
    assignments, the rows gathered, the products and the result carried
    back are that many whenever the batch's assignments to the held
    experts fit, each result row scaled by its weight and carried back
    in ``h``'s type; a batch that sends them more takes every row."""
    count = experts["w_gate"].shape[0]
    first = 0 if held is None else held[0]
    n, top_k = chosen.shape
    path, tile, sliced = expert_plan(n * top_k, experts)
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

    def every_row(_):
        with jax.named_scope("moe.dispatch"):
            src = source
            if path == "streamed":
                src = _whole_tiles(source, tile)
                visits = visit_table(load, n * top_k)
            elif path == "tiled":
                src = _whole_tiles(source, tile)
                table = tile_table(load, src.shape[0], tile)
            # every index is in range: "clip" gathers without the
            # select over the whole (assignments, E) result that
            # "fill" puts after it
            rows = jnp.take(h, src, axis=0, mode="clip")
        with jax.named_scope("moe.experts"):
            if path == "streamed":
                out = streamed_experts(rows, visits, experts, sliced)
            elif path == "tiled":
                out = tiled_experts(rows, table, experts, tile, sliced)
            else:
                out = grouped_experts(rows, load, experts)
        with jax.named_scope("moe.combine"):
            # back in the tokens' order; a row past the last group
            # holds whatever the grouped product left there: selected
            # away, not multiplied by 0
            share = jnp.where(mine, weights, 0.0)
            out = jnp.take(out, back, axis=0, mode="clip").reshape(
                n, top_k, -1)
            y = jnp.sum(jnp.where(mine[..., None],
                                  out.astype(jnp.float32)
                                  * share[..., None], 0.0), axis=1)
        return y.astype(h.dtype)

    narrow = held_rows(n * top_k, experts, routed)
    if narrow is None:
        return every_row(None), load

    def held_only(_):
        with jax.named_scope("moe.dispatch"):
            rows = jnp.take(h, source[:narrow], axis=0, mode="clip")
        with jax.named_scope("moe.experts"):
            out = tiled_experts(rows, tile_table(load, narrow, tile),
                                experts, tile, sliced)
        with jax.named_scope("moe.combine"):
            share = jnp.where(mine, weights, 0.0).reshape(-1)
            scaled = (out * jnp.take(share, order[:narrow])[:, None]
                      ).astype(h.dtype)
            # an assignment that is not held here points past the rows
            # computed (clipped) and is selected away
            out = jnp.take(scaled, back, axis=0, mode="clip").reshape(
                n, top_k, -1)
            y = jnp.sum(jnp.where(mine[..., None],
                                  out.astype(jnp.float32), 0.0), axis=1)
        return y.astype(h.dtype)

    y = lax.cond(jnp.sum(load) <= narrow, held_only, every_row, None)
    return y, load


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
    y, load = routed_experts(h, chosen, weights, p["experts"], held, live,
                             routed=p["router"].shape[-1])
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            shared = swiglu(h, p["shared"])
            if shared_scale != 1.0:
                shared = shared * jnp.asarray(shared_scale, shared.dtype)
            y = y + shared
    return y, load
