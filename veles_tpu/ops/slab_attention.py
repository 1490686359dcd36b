"""Attend each slot over its own length: the dense K/V slab's kernel.

A decode step of the slot engine (``parallel/decode._slot_steps``)
attends one query a slot against what the slot has cached. The plain
formulation (``parallel/blocks._cache_attend``) takes a rectangular
window ``leaf[..., :span]`` for ALL slots, ``span`` the longest live
slot's length rounded up: a slot at 200 positions beside one at 890
reads, widens to float32 and multiplies 896. The work the bytes ask
for is each slot's own prefix, once.

:func:`slab_attend` is that, as one Pallas TPU kernel a block. It takes
a block's ``k`` and ``v`` leaves whole, as the slab holds them
(``(S, H·D, T)``, heads and ``head_dim`` folded, positions minor: no
transpose, no copy, no slice that XLA would have to materialize in
front of a custom call) and walks the LIVE tiles of positions only:
``ceil(length / tile)`` tiles of slot 0, then slot 1's, in one loop
whose trip count is their sum. Tile ``i + 1`` is on its way from HBM
(DMAs of ``(H·D, 128)`` in the leaves' own type, as many as the slot's
length reaches into the tile) while tile ``i`` is multiplied, across
slot boundaries too, so the stream never stops and a tile past a
slot's length costs neither a DMA nor arithmetic nor a grid step. The
static ``span`` only bounds the table of visits.

Formulation (what Mosaic takes on a v5e and what keeps the MXU out of
the way of the DMA): positions lie on lanes and ``H·D`` on sublanes,
so the scores of all heads are ONE plain matrix product,
``Q (H, H·D) @ K (H·D, tile)``, with ``Q`` the query laid out block
diagonally (row ``h`` holds head ``h``'s ``D`` values at columns
``h·D..``, zeros elsewhere), and the weighted sum is one more,
``p (H, tile) · V (H·D, tile)ᵀ``, of which the block diagonal is the
answer. No batch dimension (``ops/paged_attention.py`` says what
Mosaic refuses), no widening of the tile: operands in the leaves' type,
products accumulated in float32, an online softmax (running max and
sum, float32) across a slot's tiles, ``1/sqrt(D)`` applied to the
float32 scores, positions at or past the slot's length masked to an
exact zero. ``p`` is rounded to the leaves' type before ``p · V``,
as ``_cache_attend`` rounds it.

The chunk's staged columns (``n`` new positions a slot, in a buffer of
their own) are a hundredth of the bytes: the kernel returns its sum
un-normalised with the running max and sum, and :func:`join_tail`
merges the staged columns in ``jnp`` by the usual log-sum-exp rule: one
softmax over both parts, as ``_cache_attend(tail=...)`` promises. A
slot with length 0 (an idle lane) reads nothing and gives
``(0, -1e30, 0)``; the tail always holds a visible column, so the
merge never divides by zero.

:func:`use_slab_kernel` is the rule (``ops/platform.py``'s
convention): the kernel on a TPU whose VMEM it knows, for float leaves
of the folded shape, lane-aligned, known to lie on one device.
Everything else keeps ``_cache_attend``. A test that wants the kernel
on the CPU patches this module's ``on_tpu`` and ``device_kind``; the
kernel then runs interpreted.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.platform import (VMEM_MIB, device_kind, on_tpu,
                                    pallas_interpret)

#: positions a visit takes from each leaf. Swept on the v5e at the
#: benchmark's serving shapes (16 slots, 16 heads of 64, bfloat16,
#: ragged lengths 80..1,020; PERF.md §6, PR 32).
TILE = 256

#: positions one DMA brings: a tile comes in pieces, and a piece past
#: the slot's length does not come (the lane width: the least a DMA of
#: a positions-minor leaf can take)
_PIECE = 128
assert TILE % _PIECE == 0

def vmem_claim():
    """VMEM the call claims, in bytes: 25/32 of the chip's (100 of a
    v5e's 128 MiB), or None where the chip's is not known. Far more
    than the kernel uses (two buffers a leaf, 2 MB at the serving
    shapes). Left room, XLA's memory-space assignment copies a kernel
    operand that fits into VMEM ahead of the call: a WHOLE leaf (32 MB
    at the serving shapes, 19 of a chunk's 48 when compiled for a
    v5e), every position of every slot, which is the read this kernel
    exists to spare; an operand's declared memory space does not stop
    it (libtpu 0.0.34). With 100 of 128 MiB claimed no such leaf fits
    beside the call and none is copied (``tests/test_tpu_lowering.py``
    holds the chunk program to that), and the 28 MiB left are what
    XLA prefetches the step's weights through: enough (PERF.md §6, PR
    32: on the v5e the step and the weights' waits read the same at
    this claim and at 64 MiB; at 120 MiB the weights come in line and
    the step is 13% slower)."""
    mib = VMEM_MIB.get(device_kind())
    return mib and (mib << 20) * 25 // 32


#: lanes of the float32 statistics the kernel returns (a lane-dense
#: block; every lane holds the same number)
_STAT_LANES = 128


def use_slab_kernel(leaf, sharding):
    """Whether a decode step's attend over the block leaf ``leaf``
    (an array, a tracer or a shape), which lies as ``sharding`` says,
    runs :func:`slab_attend`: on a TPU whose VMEM is known and holds
    the kernel's buffers four times over, for a float leaf in the
    folded shape ``(S, H·D, T)`` (the int8-KV tier's leaves are
    ``(S, H, D, T)`` int8 with scales), ``H·D`` a whole multiple of
    128 (the tile is one) and ``T`` whole tiles, and the leaf on ONE
    device (a bare ``pallas_call`` cannot be partitioned over
    head-sharded leaves).

    ``sharding`` comes from whoever knows the place: the decoder reads
    it off its state's arrays, a slot program is built for one place
    (``parallel/decode.slot_fns``) and told it. A tracer cannot say (a
    jit that shards its arguments over a mesh's Auto axes leaves
    nothing of it in their types), so ``None``, a place nobody knows,
    keeps ``_cache_attend`` too. Read when a program is traced, and by
    the decoder for its books, with the same two arguments: no flag,
    key or option chooses."""
    if not on_tpu() or sharding is None or len(sharding.device_set) != 1 \
            or leaf.ndim != 3 \
            or leaf.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    _, width, max_len = leaf.shape
    claim = vmem_claim()
    # two buffers a leaf, K and V
    buffers = 4 * width * TILE * jnp.dtype(leaf.dtype).itemsize
    return width % 128 == 0 and max_len % TILE == 0 \
        and claim is not None and 4 * buffers <= claim


def visit_table(lengths, span):
    """The kernel's walk over ``lengths`` (S,): ``(slot, tile index)``
    of each live tile in order, int32 ``(S · ceil(span / TILE),)``
    each, and the number of live tiles ``(1,)``. A slot has
    ``ceil(min(length, span) / TILE)`` live tiles; entries past the
    last live one are never visited."""
    slots = lengths.shape[0]
    n_tiles = -(-span // TILE)
    live = (jnp.clip(lengths, 0, span) + TILE - 1) // TILE
    ends = jnp.cumsum(live, dtype=jnp.int32)
    visit = jnp.arange(slots * n_tiles, dtype=jnp.int32)
    # (a comparison and a sum: the table is a few hundred entries)
    slot = jnp.minimum(
        jnp.sum(visit[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        slots - 1)
    at = visit - jnp.take(ends - live, slot)
    return slot, at, ends[-1:]


def _block_diagonal(heads, width):
    """bool ``(H, H·D)``: column ``c`` belongs to head ``c // D``."""
    return lax.broadcasted_iota(jnp.int32, (heads, width), 1) \
        // (width // heads) \
        == lax.broadcasted_iota(jnp.int32, (heads, width), 0)


def _walk_kernel(slot_ref, at_ref, total_ref, len_ref, *refs, scale,
                 ring):
    """All live tiles, in turn. ``refs``: in a ``ring`` two more
    scalars a slot, where its ring starts (``before mod T``) and the
    least age an entry it sees has (:func:`slab_attend`); then
    ``q_ref`` (S, 1, H·D) or, grouped, a slot's query heads laid out
    by their K/V head (S, H, H_kv·D), and the three results, whole in
    VMEM; ``k_hbm``/``v_hbm`` stay where they lie and each visit's
    ``(H_kv·D, tile)`` windows are copied, as far as the slot's length
    reaches into them, into one of two buffers while the other is
    multiplied."""
    if ring:
        start_ref, low_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, acc_out, m_out, l_out, k_buf, v_buf, sem,
     acc_ref, m_ref, l_ref) = refs
    heads, width = acc_ref.shape
    tile = TILE
    total = total_ref[0]
    # a slot with no live tile keeps these
    acc_out[...] = jnp.zeros_like(acc_out)
    m_out[...] = jnp.full_like(m_out, -1e30)
    l_out[...] = jnp.zeros_like(l_out)

    # what a copy does not bring is what an earlier visit left, and
    # before any visit whatever the memory held: masked to an exact
    # zero weight, which times a NaN is a NaN
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)

    def copies(visit, buf, do):
        """``do`` ("start" or "wait") to the visit's DMAs: a leaf's
        tile comes in pieces of ``_PIECE`` positions, and only the
        pieces that begin before the slot's length come at all."""
        s = slot_ref[visit]
        length = len_ref[s]
        for piece in range(tile // _PIECE):
            first = pl.multiple_of(at_ref[visit] * tile + piece * _PIECE,
                                   _PIECE)

            def both(piece=piece, first=first):
                for leaf, (hbm, into) in enumerate(
                        ((k_hbm, k_buf), (v_hbm, v_buf))):
                    getattr(pltpu.make_async_copy(
                        hbm.at[s, :, pl.ds(first, _PIECE)],
                        into.at[buf, :, pl.ds(piece * _PIECE, _PIECE)],
                        sem.at[leaf, buf, piece]), do)()

            # (a visit's first piece is live, or it were no visit)
            both() if piece == 0 else pl.when(first < length)(both)

    @pl.when(total > 0)
    def _first():
        copies(0, 0, "start")

    def visit_one(visit, carry):
        buf = visit % 2

        @pl.when(visit + 1 < total)
        def _next():
            copies(visit + 1, 1 - buf, "start")

        copies(visit, buf, "wait")
        s, at = slot_ref[visit], at_ref[visit]
        length = len_ref[s]

        @pl.when(at == 0)
        def _reset():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, -1e30)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = q_ref[s]                            # (1, H·D) or (H, H_kv·D)
        k, v = k_buf[buf], v_buf[buf]                       # (H·D, tile)
        grouped = q.shape[0] > 1
        if grouped:
            q_heads = q
        else:
            diagonal = _block_diagonal(heads, width)
            # (the select in float32: Mosaic has no bfloat16 mask layout)
            q_heads = jnp.where(
                diagonal, jnp.broadcast_to(q.astype(jnp.float32),
                                           (heads, width)),
                0.0).astype(q.dtype)
        scores = jnp.dot(q_heads, k,
                         preferred_element_type=jnp.float32) * scale
        position = at * tile + lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        seen = position < length
        if ring:
            # an entry's age: how far past the ring's start it lies
            age = position - start_ref[s]
            age = jnp.where(age < 0, age + k_hbm.shape[-1], age)
            seen &= age >= low_ref[s]
        scores = jnp.where(seen, scores, -1e30)
        # the online merge; a live tile holds a visible position, so
        # -1e30 underflows to an exact zero against the running max
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new

        @pl.when((at + 1) * tile >= length)
        def _emit():
            if grouped:
                # query head h's sum is columns c·D.. of row h, c its
                # K/V head
                head_dim = acc_out.shape[-1]
                group = heads * head_dim // width
                mine = lax.broadcasted_iota(
                    jnp.int32, (heads, head_dim), 0) // group
                acc_out[s] = sum(
                    jnp.where(mine == c,
                              acc_ref[:, c * head_dim:(c + 1) * head_dim],
                              0.0)
                    for c in range(width // head_dim))
            else:
                # head h's sum is columns h·D.. of row h
                acc_out[s] = jnp.sum(
                    jnp.where(diagonal, acc_ref[...], 0.0), axis=0,
                    keepdims=True)
            m_out[s] = jnp.broadcast_to(m_ref[...], m_out.shape[1:])
            l_out[s] = jnp.broadcast_to(l_ref[...], l_out.shape[1:])
        return carry

    lax.fori_loop(0, total, visit_one, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "claim", "scope"))
def _walk(visits, lengths, q, k, v, interpret, claim, ring=(), scope=None):
    """The ``pallas_call`` over :func:`visit_table`'s ``visits``. A
    function jitted on its own, so that a program that attends in
    every block lowers the kernel once and calls it: lowered once a
    block, 24 lowerings cost a chunk program 3.5 s of every set-up,
    compile cache warm or not (PERF.md §6). Grouped heads (the
    leaves' ``H_kv·D`` less than the queries' ``H·D``) come laid out
    by their K/V head: row ``h`` holds query head ``h`` at the columns
    of its K/V head, zeros elsewhere. ``scope`` is the caller's own
    scope, named again inside the jit: a reader of the scope table
    knows an op by the innermost names of its op_name, and ``_walk``
    would be one of them (None: no name)."""
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        return _walk_call(visits, lengths, q, k, v, interpret, claim,
                          ring)


def _walk_call(visits, lengths, q, k, v, interpret, claim, ring):
    """:func:`_walk`'s body."""
    slots, _, heads, head_dim = q.shape
    width = k.shape[1]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    stats = jax.ShapeDtypeStruct((slots, heads, _STAT_LANES), jnp.float32)
    if width == heads * head_dim:
        rows, summed = q.reshape(slots, 1, width), (slots, 1, width)
    else:
        kv = width // head_dim
        mine = jnp.arange(heads)[:, None] // (heads // kv) \
            == jnp.arange(kv)[None, :]
        rows = jnp.where(mine[None, :, :, None], q[:, 0, :, None, :],
                         0).astype(q.dtype).reshape(slots, heads, width)
        summed = (slots, heads, head_dim)
    acc, m, l = pl.pallas_call(
        functools.partial(_walk_kernel, scale=head_dim ** -0.5,
                          ring=bool(ring)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 + len(ring), grid=(),
            in_specs=[whole, pl.BlockSpec(memory_space=pltpu.HBM),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=[whole, whole, whole],
            scratch_shapes=[pltpu.VMEM((2, width, TILE), k.dtype),
                            pltpu.VMEM((2, width, TILE), v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2, TILE // _PIECE)),
                            pltpu.VMEM((heads, width), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(summed, jnp.float32),
                   stats, stats],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=claim),
        name="slab_attend",
        interpret=interpret,
    )(*visits, lengths, *ring, rows, k, v)
    return acc.reshape(slots, heads, head_dim), m[..., 0], l[..., 0]


def slab_attend(q, k, v, lengths, span, interpret=None, ring=None,
                scope=None):
    """One query a slot against the first ``lengths[s]`` cached
    positions of slot ``s``. ``q`` (S, 1, H, D); ``k``, ``v`` one
    block's leaves (S, H·D, T), or of grouped heads (S, H_kv·D, T),
    query head ``h`` on K/V head ``h // (H / H_kv)``; whole;
    ``lengths`` (S,) int32, at most ``span`` counted (static: the
    longest a slot can be). A ``ring`` (``(start, low)``, (S,) int32
    each) sees of its first ``lengths[s]`` entries those whose age,
    ``(entry - start) mod T``, is at least ``low``
    (``parallel/decode.ring_visible``). Returns the softmax's parts,
    float32: ``(acc (S, H, D), m (S, H), l (S, H))`` with ``acc =
    sum_p exp(score_p - m) · v_p`` and ``l`` the same sum without
    ``v``; a slot of length 0 gives ``(0, -1e30, 0)``. ``scope`` names
    the kernel's ops once more inside its jit (the caller's innermost
    scope). ``interpret=None`` resolves from the platform."""
    if interpret is None:
        interpret = pallas_interpret()
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, span)
    return _walk(visit_table(lengths, span), lengths, q, k, v,
                 interpret=interpret, claim=vmem_claim(),
                 ring=tuple(part.astype(jnp.int32) for part in ring or ()),
                 scope=scope)


def join_tail(q, parts, k_tail, v_tail, visible):
    """The softmax over the cached prefix AND the staged columns:
    ``parts`` is :func:`slab_attend`'s ``(acc, m, l)``; ``k_tail``,
    ``v_tail`` (S, H, D, n) the chunk's staged columns as the leaves
    hold them, heads apart; ``visible`` bool (S, 1, 1, n). Returns
    ``(S, 1, H, D)`` float32, ``_cache_attend(tail=...)``'s result."""
    acc, m, l = parts
    scores = jnp.einsum("bqhd,bhdk->bhqk", q, k_tail.astype(q.dtype),
                        preferred_element_type=jnp.float32) \
        * q.shape[-1] ** -0.5
    scores = jnp.where(visible, scores, -1e30)[:, :, 0]     # (S, H, n)
    m_all = jnp.maximum(m, jnp.max(scores, axis=-1))
    alpha = jnp.exp(m - m_all)[..., None]
    p = jnp.exp(scores - m_all[..., None])
    out = alpha * acc + jnp.einsum(
        "bhk,bhdk->bhd", p.astype(q.dtype), v_tail.astype(q.dtype),
        preferred_element_type=jnp.float32)
    total = alpha * l[..., None] + jnp.sum(p, axis=-1, keepdims=True)
    return (out / total)[:, None]
