"""Write a chunk's staged K/V columns to the dense slab: one Pallas call.

A chunk of the slot engine (``parallel/decode._slot_steps``) stages
each step's new column of every slot in a small ``(S, W, n)`` buffer a
leaf, and when its ``n`` steps are done, writes slot ``s``'s block of
``n`` columns to the leaf ``(S, W, T)`` (positions minor) at the
length the slot had when the chunk began, ``before[s]``, clamped onto
the lane's end as ``lax.dynamic_update_slice`` clamps it. Written as
XLA ops, that is one strided write per slot and leaf, each an op of
its own in a loop over the slots: 768 serial writes a chunk at the
benchmark's GPT-2 sizes (16 slots × 48 leaves), ~3.5 µs each on a v5e
where the bytes they move take nanoseconds (:func:`write_blocks_loop`,
the path everything but the kernel's rule keeps).

:func:`write_blocks` is the same write as one ``pallas_call`` over
all the leaves of a shape: a grid step a slot, and in it every leaf's
DMAs in flight at once. Mosaic takes no DMA of a positions-minor leaf
narrower than the lane width (128), nor of a staged buffer's ``n``
columns (its memory is padded to 128 lanes and a slice of it is
refused: "Slice shape along dimension 2 must be aligned to tiling
(128)"; PERF.md §6, PR 38). So each block is a read–modify–write of the
128-lane piece that holds ``[start, start + n)``, and of the next
piece too where the block straddles their boundary: the piece comes
into VMEM, the staged columns are put at lane ``start % 128`` (one
lane rotation of the block, padded to 128 lanes, and a select, both in
32-bit words, so the bits are moved and never converted) and the piece
goes back. Lanes outside the block are sent back as they came, so the
leaf after the call is bitwise what the loop leaves, idle lanes
included. Each leaf is its own result's buffer
(``input_output_aliases``): a caller that lets go of it (the chunk's
carry) has it written where it lies.

:func:`use_write_kernel` is the rule (``ops/platform.py``'s
convention): the kernel on a TPU whose VMEM is known, for a float leaf
of rank 3 whose ``W`` is whole sublane tiles and whose ``T`` is whole
lane tiles, ``n`` at most a piece, known to lie on one device.
Everything else keeps the loop. A test that wants the kernel on the
CPU patches this module's ``on_tpu`` and ``device_kind``; the kernel
then runs interpreted.

A leaf may be a RING (a window layer's, ``parallel/blocks.Windowed``):
position ``p`` lies at ``p mod T``, and a block goes to ``before[s] mod
T``. Where it wraps, the kernel's second piece is the leaf's first
(``T`` is whole pieces, so a piece never wraps), and the loop writes
its columns each where it lies. The ring's write is ``cache.ring``
inside ``cache.append``.
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

#: positions one DMA of a positions-minor leaf takes: the least Mosaic
#: takes (the lane width)
_PIECE = 128

#: rows of a sublane tile, by the leaves' type
_SUBLANES = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16}


def vmem_claim():
    """VMEM the call claims, in bytes, or None where the chip's is not
    known: 25/32 of it (100 of a v5e's 128 MiB), as
    ``ops/slab_attention.vmem_claim`` claims and for its reason: with
    room left, XLA's memory-space assignment copies a whole leaf that
    fits into VMEM ahead of a kernel that takes it, which is the read
    of every position this kernel exists to spare."""
    mib = VMEM_MIB.get(device_kind())
    return mib and (mib << 20) * 25 // 32


def _leaf_vmem(leaf, n):
    """VMEM a leaf takes in the call: its piece and its block set at
    its lanes, and the pipeline's two blocks of a slot's staged rows,
    ``n`` rows of ``W`` padded to whole sublane tiles."""
    _, width, _ = leaf.shape
    tile = _SUBLANES[jnp.dtype(leaf.dtype)]
    return (2 * _PIECE + 2 * -(-n // tile) * tile) * width \
        * jnp.dtype(leaf.dtype).itemsize


def use_write_kernel(leaf, sharding, n):
    """Whether a chunk of ``n`` staged columns a slot goes to the
    positional leaf ``leaf`` (an array, a tracer or a shape, ``(S, W,
    T)``), which lies as ``sharding`` says (None: nobody knows), by
    :func:`write_blocks`: on a TPU whose VMEM is known and holds the
    leaf's buffers, for a float leaf of rank 3 (the int8-KV tier's
    ``(S, H, D, T)`` int8 leaves are not), ``W`` whole sublane tiles,
    ``T`` whole pieces, ``n`` at most one piece (a block then lies in
    one piece or two), and the leaf on ONE device (a bare
    ``pallas_call`` cannot be partitioned). Read when a program is
    traced, and by the decoder for its books, with the same arguments:
    no flag, key or option chooses."""
    if not on_tpu() or sharding is None or len(sharding.device_set) != 1 \
            or leaf.ndim != 3 or jnp.dtype(leaf.dtype) not in _SUBLANES:
        return False
    _, width, max_len = leaf.shape
    claim = vmem_claim()
    return width % _SUBLANES[jnp.dtype(leaf.dtype)] == 0 \
        and max_len % _PIECE == 0 and 0 < n <= _PIECE \
        and claim is not None and _leaf_vmem(leaf, n) <= claim // 2


def _words(x):
    """``x`` as 32-bit words: what a lane rotation takes, and what a
    select moves bit for bit."""
    return pltpu.bitcast(x, jnp.uint32)


def _block_start(before, max_len, n):
    """Where a slot's block of ``n`` columns starts: at ``before``,
    clamped onto the lane's end as ``lax.dynamic_update_slice`` clamps
    it (only a sequence past its budget, whose tokens the host
    discards, stands there). A ring's starts at ``before mod
    max_len``."""
    return jnp.clip(before, 0, max_len - n)


def _straddles(off, n):
    """Whether a block at lane ``off`` of its piece reaches into the
    next piece."""
    return off + n > _PIECE


def _write_kernel(before_ref, *refs, count, n, ring):
    """Slot ``program_id(0)``'s block of every leaf. ``refs``: the
    ``count`` leaves where they lie, the staged blocks of this slot in
    VMEM ``(count, 1, n, W)`` (the columns as rows, as the chunk's
    carry holds them), the leaves again as the results (the same
    buffers), then scratch: the pieces ``(count, W, 128)``, the blocks
    set at their lanes ``(count, W, 128)``, a ``(W, 128)`` pad and the
    DMA semaphores ``(2, count)``. In a ``ring`` the piece after the
    last is the first. A leaf's DMAs are code of their own;
    its arithmetic is one body in a loop over the leaves (a body a
    leaf, unrolled, is a kernel that Mosaic takes seconds to compile
    again in every program that holds it: PERF.md §6, PR 38)."""
    leaves, staged, out = refs[:count], refs[count], \
        refs[count + 1:2 * count + 1]
    piece_buf, placed, pad, sem = refs[2 * count + 1:]
    s = pl.program_id(0)
    max_len = leaves[0].shape[-1]
    start = before_ref[s] % max_len if ring \
        else _block_start(before_ref[s], max_len, n)
    base = start // _PIECE * _PIECE
    off = start - base

    def copies(p, back):
        """The DMAs of the ``p``-th piece from ``base`` of every leaf:
        into ``piece_buf`` or, ``back``, out of it."""
        at = base + p * _PIECE
        if ring:
            at = at % max_len
        window = pl.ds(pl.multiple_of(at, _PIECE), _PIECE)
        return [pltpu.make_async_copy(
            *((piece_buf.at[leaf], out[leaf].at[s, :, window]) if back
              else (leaves[leaf].at[s, :, window], piece_buf.at[leaf])),
            sem.at[int(back), leaf]) for leaf in range(count)]

    for copy in copies(0, False):
        copy.start()

    def place(leaf, carry):
        # the staged columns at lanes [0, n) of the pad, turned so that
        # column c stands at lane (off + c) % 128: the block's lanes of
        # the first piece and, past the boundary, of the next
        pad[:, :n] = staged[leaf, 0].T
        placed[leaf] = pltpu.bitcast(
            pltpu.roll(_words(pad[...]), off, 1), placed.dtype)
        return carry

    lax.fori_loop(0, count, place, 0)

    def piece(p, carry):
        """The ``p``-th piece of every leaf: in (the first's reads are
        in flight already), the block's lanes of it set, and out."""
        ins, outs = copies(p, False), copies(p, True)

        @pl.when(p > 0)
        def _read():
            for copy in ins:
                copy.start()

        for copy in ins:
            copy.wait()

        def put(leaf, carry):
            turned = _words(placed[leaf])
            lane = lax.broadcasted_iota(jnp.int32, turned.shape, 1) \
                + p * _PIECE
            piece_buf[leaf] = pltpu.bitcast(
                jnp.where((lane >= off) & (lane < off + n), turned,
                          _words(piece_buf[leaf])), piece_buf.dtype)
            return carry

        lax.fori_loop(0, count, put, 0)
        for copy in outs:
            copy.start()
        for copy in outs:
            copy.wait()
        return carry

    # (start + n <= T: a second piece lies inside the leaf; in a ring
    # it may be the first)
    lax.fori_loop(0, 1 + _straddles(off, n).astype(jnp.int32), piece, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "claim", "ring"))
def _write(before, leaves, staged, interpret, claim, ring=False):
    """The ``pallas_call`` over leaves of one shape and type, their
    staged columns stacked as rows ``(count, S, n, W)``. A function
    jitted on its own, so that a program lowers the kernel once
    (``ops/slab_attention._walk`` has the cost of not doing so)."""
    count = len(leaves)
    slots, width, _ = leaves[0].shape
    n = staged.shape[2]
    where = pl.BlockSpec(memory_space=pltpu.HBM)
    block = pl.BlockSpec((count, 1, n, width),
                         lambda s, before: (0, s, 0, 0))
    call = pl.pallas_call(
        functools.partial(_write_kernel, count=count, n=n, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slots,),
            in_specs=[where] * count + [block],
            out_specs=[where] * count,
            scratch_shapes=[
                pltpu.VMEM((count, width, _PIECE), leaves[0].dtype),
                pltpu.VMEM((count, width, _PIECE), leaves[0].dtype),
                pltpu.VMEM((width, _PIECE), leaves[0].dtype),
                pltpu.SemaphoreType.DMA((2, count))]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
                   for leaf in leaves],
        input_output_aliases={1 + i: i for i in range(count)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=claim),
        name="slab_write",
        interpret=interpret)
    # the scope again, inside the jit: a reader of the scope table
    # knows an op by the innermost names of its op_name, and
    # ``jit(_write)`` would be one of them
    with jax.named_scope("cache.append"), _ring_scope(ring):
        return tuple(call(before, *leaves, staged))


def _ring_scope(ring):
    """``cache.ring`` around a ring's write, nothing around another."""
    return jax.named_scope("cache.ring") if ring \
        else contextlib.nullcontext()


def write_blocks(leaves, staged, before, interpret=None, rings=None):
    """Slot ``s``'s ``n`` staged columns of each leaf, written at
    ``min(before[s], T - n)``: ``leaves`` (S, W, T) and ``staged``
    (S, W, n) sequences of the same length, leaf by leaf; ``before``
    (S,) int32. Returns the leaves written, in order. One call for the
    leaves of a shape and type, or as many as the claim's half holds
    at once (one at every shape served). ``rings`` says of each leaf
    whether it is a ring (None: none is). ``interpret=None`` resolves
    from the platform."""
    if interpret is None:
        interpret = pallas_interpret()
    claim = vmem_claim()
    budget = claim // 2 if claim else None
    before = before.astype(jnp.int32)
    groups = {}
    for i, leaf in enumerate(leaves):
        ring = bool(rings and rings[i])
        groups.setdefault((leaf.shape, jnp.dtype(leaf.dtype), ring),
                          []).append(i)
    out = list(leaves)
    for (_, _, ring), kept in groups.items():
        n = staged[kept[0]].shape[-1]
        many = max(1, budget // _leaf_vmem(leaves[kept[0]], n)) \
            if budget else len(kept)
        for at in range(0, len(kept), many):
            part = kept[at:at + many]
            # (the columns as rows, in one array: what the carry
            # holds, and what a staged block of a slot is without
            # padding to 128 lanes)
            for i, leaf in zip(part, _write(
                    before, [leaves[i] for i in part],
                    jnp.stack([jnp.swapaxes(staged[i], 1, 2)
                               for i in part]),
                    interpret=interpret, claim=claim, ring=ring)):
                out[i] = leaf
    return out


def write_blocks_loop(leaves, staged, before, rings=None):
    """:func:`write_blocks` as XLA ops: for each leaf, a loop over the
    slots of one ``dynamic_update_slice`` each (which clamps the start
    onto the lane's end); a ring's block (``rings``) a write of its
    columns each at its place. The path of everything the rule does
    not give the kernel."""
    slots = before.shape[0]

    def put(s, leaf, block):
        at = (s,) + (0,) * (leaf.ndim - 2) + (before[s],)
        return lax.dynamic_update_slice(
            leaf, lax.dynamic_slice_in_dim(block, s, 1, 0), at)

    def put_ring(s, leaf, block):
        at = (before[s] + jnp.arange(block.shape[-1])) % leaf.shape[-1]
        return leaf.at[s, ..., at].set(jnp.moveaxis(block[s], -1, 0))

    out = []
    for i, (leaf, block) in enumerate(zip(leaves, staged)):
        ring = bool(rings and rings[i])
        with _ring_scope(ring):
            out.append(lax.fori_loop(
                0, slots, functools.partial(put_ring if ring else put,
                                            block=block), leaf))
    return out
