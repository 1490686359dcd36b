"""Fused ragged paged attention: the page-table walk moves into the kernel.

The paged slot engine (``parallel/kv_pool.py``) attends each decode
step over a GATHERED span: every slot's pages are materialized into a
contiguous ``(S, PB * page_size, H, D)`` buffer sized to the LONGEST
live sequence, and masking zeroes the overshoot. The math is exact,
but the dispatched work is not — a slot at length 40 in a batch whose
longest neighbor spans 12 pages attends (and gathers HBM for) all 12,
and the servescope waste plane names the bill precisely:
``span_overshoot``/``page_overshoot`` (ROADMAP item 5; PR 15's
decomposition).

This module deletes that overshoot at the kernel level (the ACT lesson,
PAPERS.md arxiv 2510.09932 — accelerator-specific codegen behind a
capability probe with a portable fallback):

- :func:`paged_attend` / :func:`paged_attend_int8` — Pallas
  flash-style kernels gridded over ``(slot, page)`` that walk the page
  table DIRECTLY: the table and the per-slot live lengths ride as
  prefetched scalars (``PrefetchScalarGridSpec``), each grid cell DMAs
  exactly one physical page into VMEM (the index map reads
  ``page_table[s, p]`` — no gathered copy of the pool ever exists),
  and an online-softmax accumulator (running ``(acc, m, l)`` à la
  flash attention) merges a slot's pages left to right. Pages past a
  slot's live count are SKIPPED (``pl.when`` — the copy of scratch
  page 0 still streams, but zero FLOPs run), so attended work scales
  with each slot's live tokens, not the padded max-span.
- :func:`use_paged_kernel` — the rule: the kernel on the TPU with no
  serve mesh. Everywhere else — the CPU, and any serve mesh — the
  established gather path runs unchanged: it IS the CPU bit-identity
  contract (tests/test_paged.py), and interpret mode executes these
  kernels on CPU to prove the kernel path's token streams match it
  (tests/test_paged_kernel.py, marked ``slow``).

Formulation (what Mosaic takes, libtpu 0.0.34): decode attention with
equal Q and KV heads is one matrix-VECTOR product per head, so the
float tier never touches the MXU — scores are a VPU multiply of the
``(ps, H, D)`` page by the broadcast ``(H, D)`` query and a lane
reduction, all in the pool's own layout, no transpose and no dot. The
int8 tier keeps its head-major ``(H, D, ps)`` page and runs the two
contractions as head-BATCHED dots with the batch dimension leading on
both operands (``(H,1,D)·(H,D,ps)`` and ``(H,1,ps)·(H,D,ps)``). The
first version batched over a MIDDLE dimension (``(bh,D)·(ps,bh,D)``),
which Mosaic refused at lowering: ``failed to parse
TPU_DotDimensionNumbersAttr`` — it had only ever run interpreted.

Numerical contract: the online-softmax merge is algebraically the
gather path's masked softmax (masked positions contribute EXACT zeros
— every live page holds at least one visible position, so the -1e30
sentinels underflow to 0 against the running max), but the
accumulation ORDER differs, so logits agree to f32 round-off rather
than bitwise. The bit-identity the serving tier promises is at the
TOKEN level and proven empirically in interpret mode; the probe keeps
CPU serving on the gather path, so the repo's tier-1 contract is
untouched.

See docs/paged_kv.md ("The fused kernel") and ``make paged-kernel``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.platform import on_tpu, pallas_interpret

#: Test seam: None = the rule; True/False stand in for the platform so
#: the interpret-mode tests engage the kernel on the CPU (and pin the
#: gather beside it). Flipping it does NOT invalidate already-traced
#: programs: the rule is read at TRACE time inside
#: ``_paged_slot_step``, so tests toggling it must
#: ``jax.clear_caches()``.
FORCE_PAGED_KERNEL = None


def use_paged_kernel(mesh=None):
    """Whether paged dispatches run the fused kernel: on the TPU with
    no serve ``mesh`` (the gather path is the portable fallback AND the
    CPU bit-identity reference). Read at trace time by
    ``kv_pool._paged_slot_step`` — no jitted signature carries it, so
    the AOT facade and the ``paged.*`` instrument names extend
    unchanged.

    Under a serve ``mesh`` the answer is the gather whatever the seam
    says: the sharded paged programs are plain GSPMD jits, and a bare
    ``pallas_call`` is a custom call XLA cannot partition over the
    head-sharded pool."""
    if mesh is not None:
        return False
    if FORCE_PAGED_KERNEL is not None:
        return FORCE_PAGED_KERNEL
    return on_tpu()


# -- the kernels --------------------------------------------------------------

def _online_merge(acc_ref, m_ref, l_ref, scores, axis, weighted_values):
    """One flash-attention merge step: fold this page's masked
    ``scores`` (positions along ``axis``, -1e30 where masked) and the
    value product ``weighted_values(p) -> acc-shaped`` into the running
    ``(acc, m, l)`` accumulators. -1e30 underflows to an EXACT zero
    against the running max (every live page has at least one visible
    position, so the max is always a real score)."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev,
                        jnp.max(scores, axis=axis, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    acc_ref[...] = alpha * acc_ref[...] + weighted_values(p)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=axis,
                                              keepdims=True)
    m_ref[...] = m_new


def _page_cell(len_ref, o_ref, acc_ref, m_ref, l_ref, page_size, merge):
    """The grid-cell skeleton both tiers share. Cell (slot s, logical
    page p): reset the accumulators at the slot's first page, run
    ``merge(p, length)`` on LIVE pages only, write the slot's output at
    its last page. Live pages = length // page_size + 1: the append for
    THIS step landed at position ``length`` before the attend — the
    gather path's ``arange(span) <= lengths`` contract."""
    s = pl.program_id(0)
    p = pl.program_id(1)
    length = len_ref[s]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(p <= length // page_size)
    def _merge():
        merge(p, length)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(o_ref.shape)


def _float_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size, head_dim):
    """Float tier, all on the VPU in the pool's ``(ps, H, D)`` layout:
    positions on the major axis, heads on sublanes, head_dim on lanes.
    Accumulators are ``(1, H, D)`` / ``(1, H, 1)`` f32 (the gather
    path's ``preferred_element_type`` discipline)."""
    def merge(p, length):
        q = q_ref[...].astype(jnp.float32)             # (1, H, D)
        k = k_ref[0].astype(jnp.float32)               # (ps, H, D)
        v = v_ref[0].astype(jnp.float32)
        scores = jnp.sum(k * q, axis=2, keepdims=True) \
            * (1.0 / math.sqrt(float(head_dim)))       # (ps, H, 1)
        idx = p * page_size + lax.broadcasted_iota(
            jnp.int32, scores.shape, 0)
        scores = jnp.where(idx <= length, scores, -1e30)
        _online_merge(
            acc_ref, m_ref, l_ref, scores, 0,
            lambda pw: jnp.sum(pw * v, axis=0, keepdims=True))

    _page_cell(len_ref, o_ref, acc_ref, m_ref, l_ref, page_size, merge)


def _int8_kernel(pt_ref, len_ref, q_ref, kq_ref, ks_ref, vq_ref,
                 vs_ref, o_ref, acc_ref, m_ref, l_ref, *, page_size):
    """The int8-KV twin (head-major pages ``(P, H, D, ps)`` q8 +
    ``(P, H, ps)`` scales — ``quant.int8_cache_attend``'s layout and
    math, paged): two head-batched dots with the batch dim LEADING on
    both operands, dequantization fused via the per-position scales;
    the caller pre-scaled q by 1/sqrt(D) (the int8 tier's convention).
    Accumulators are ``(H, 1, D)`` / ``(H, 1, 1)`` f32."""
    def merge(p, length):
        q = q_ref[0].astype(jnp.float32)               # (H, 1, D)
        kq = kq_ref[0].astype(jnp.float32)             # (H, D, ps)
        vq = vq_ref[0].astype(jnp.float32)
        ks = ks_ref[0][:, None, :]                     # (H, 1, ps)
        vs = vs_ref[0][:, None, :]
        scores = jnp.einsum("hqd,hdk->hqk", q, kq,
                            preferred_element_type=jnp.float32) * ks
        idx = p * page_size + lax.broadcasted_iota(
            jnp.int32, scores.shape, 2)
        scores = jnp.where(idx <= length, scores, -1e30)
        _online_merge(
            acc_ref, m_ref, l_ref, scores, 2,
            lambda pw: jnp.einsum(
                "hqk,hdk->hqd", pw * vs, vq,
                preferred_element_type=jnp.float32))

    _page_cell(len_ref, o_ref, acc_ref, m_ref, l_ref, page_size, merge)


def _grid_call(kernel, page_table, lengths, tensor_args, in_specs,
               out_shape, out_block, acc_shape, stat_shape, interpret):
    """Shared pallas_call plumbing: grid ``(slots, pages)`` with the
    page table + live lengths as prefetched scalars (index maps read
    ``page_table[s, p]`` to route each cell's DMA at its physical
    page), one f32 output block per slot, online-softmax scratch in
    VMEM. Both grid dims are sequential ("arbitrary") — the scratch
    accumulators carry across the page dim and reinitialize per
    slot."""
    if interpret is None:
        interpret = pallas_interpret()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=page_table.shape,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            out_block, lambda s, p, pt, lens: (s,) + (0,) * (
                len(out_block) - 1)),
        scratch_shapes=[
            pltpu.VMEM(acc_shape, jnp.float32),
            pltpu.VMEM(stat_shape, jnp.float32),
            pltpu.VMEM(stat_shape, jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )(page_table, lengths, *tensor_args)


def paged_attend(q, k_pages, v_pages, page_table, lengths, *,
                 page_size, interpret=None):
    """Fused paged decode attention, float tier. ``q`` (S, H, D);
    ``k_pages``/``v_pages`` one block's pool leaf (P, page_size, H, D);
    ``page_table`` (S, PB) int32 physical page ids in logical order
    (padding rows point at scratch page 0); ``lengths`` (S,) int32
    live lengths (position ``lengths[s]`` — this step's append — is
    attended, the gather path's contract). Returns (S, H, D) f32 —
    ``_cache_attend``'s output, without the gather. ``interpret=None``
    resolves from the platform (compiled on the TPU, interpreted on
    the CPU)."""
    slots, heads, head_dim = q.shape
    kernel = functools.partial(
        _float_kernel, page_size=int(page_size), head_dim=head_dim)
    qspec = pl.BlockSpec((1, heads, head_dim),
                         lambda s, p, pt, lens: (s, 0, 0))
    kvspec = pl.BlockSpec((1, page_size, heads, head_dim),
                          lambda s, p, pt, lens: (pt[s, p], 0, 0, 0))
    return _grid_call(
        kernel, page_table, lengths, (q, k_pages, v_pages),
        [qspec, kvspec, kvspec], out_shape=(slots, heads, head_dim),
        out_block=(1, heads, head_dim), acc_shape=(1, heads, head_dim),
        stat_shape=(1, heads, 1), interpret=interpret)


def paged_attend_int8(q, k_q, k_scale, v_q, v_scale, page_table,
                      lengths, *, page_size, interpret=None):
    """Fused paged decode attention, int8-KV tier. ``q`` (S, H, D)
    float, ALREADY 1/sqrt(D)-scaled (the ``int8_cache_attend``
    convention); ``k_q``/``v_q`` one block's head-major pool leaf
    (P, H, D, page_size) int8 with (P, H, page_size) f32 scales.
    Returns (S, H, D) f32."""
    slots, heads, head_dim = q.shape
    kernel = functools.partial(_int8_kernel, page_size=int(page_size))
    # q and the output ride as (S, H, 1, D): the unit row is the
    # batched dots' M dimension, so no in-kernel relayout is needed
    qspec = pl.BlockSpec((1, heads, 1, head_dim),
                         lambda s, p, pt, lens: (s, 0, 0, 0))
    kvspec = pl.BlockSpec((1, heads, head_dim, page_size),
                          lambda s, p, pt, lens: (pt[s, p], 0, 0, 0))
    sspec = pl.BlockSpec((1, heads, page_size),
                         lambda s, p, pt, lens: (pt[s, p], 0, 0))
    out = _grid_call(
        kernel, page_table, lengths,
        (q[:, :, None, :], k_q, k_scale, v_q, v_scale),
        [qspec, kvspec, sspec, kvspec, sspec],
        out_shape=(slots, heads, 1, head_dim),
        out_block=(1, heads, 1, head_dim),
        acc_shape=(heads, 1, head_dim), stat_shape=(heads, 1, 1),
        interpret=interpret)
    return out[:, :, 0, :]
