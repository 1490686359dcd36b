"""Paged KV pool with shared-prefix reuse for the slot engine.

The dense slot engine (``parallel/decode.py``) backs every slot with a
``(L, S, ..., max_len)`` slab: HBM is reserved for ``slots x max_len``
whether or not tokens exist, a thousand requests sharing a system
prompt each re-prefill it, and the slab shape caps concurrency far
below what live tokens require. This module re-expresses the SAME slot
math over a single page pool (ROADMAP open item 2; *Compiler-First
State Space Duality and Portable O(1) Autoregressive Caching for
Inference*, PAPERS.md arxiv 2603.09555 — cache state as a
compiler-visible pool addressed by a page table, not a per-request
dense allocation):

- **device side**: one ``(L, pages, page_size, H, D)`` pool (int8-KV
  tier: head-major ``(L, pages, H, D, page_size)`` q8 + per-position
  scales, exactly the dense slab's recipe) plus the slot control
  leaves. Attention is a page-table GATHER over each slot's live pages;
  appends are the same per-slot ``dynamic_update_slice`` as the dense
  engine, targeted through the page table. One compiled program per
  (bucket, group, pages-per-slot bucket), so the ``observe/xla_stats``
  counters and the no-recompile-storm guarantees carry over.
- **host side**: :class:`PagePool` — free list, per-page refcounts, the
  LRU :class:`PrefixCache` (token prefixes hashed at page granularity),
  page reservations for pool-aware admission control, and the
  page-release-rate window that prices ``Retry-After``.

Numerical contract (the existing CPU bit-identity idiom, extended):
masked positions contribute EXACT zeros to the softmax, and gathered
pages reproduce the slab values bit-for-bit, so paged ``slot_step`` /
``slot_admit_many`` stream tokens identical to the dense engine and to
greedy ``generate()`` on CPU — including shared-prefix admissions,
whose unique tail runs a prefix-masked forward over the pooled prefix
pages (``tests/test_paged.py`` pins all of it, bf16 and int8-KV).

Sharing rules (docs/paged_kv.md): only WHOLE pages are shared, the
divergent / partial tail always prefills into fresh pages, and a
slot's appends land at positions past its prompt — so a shared page is
never written by construction (copy-on-write degenerates to
"divergence allocates, sharing never mutates"). The int8-KV tier
reuses prefixes only at exact-prompt granularity: its pool stores
ROUNDED K/V while the dense prefill attends exact values, so a
partial-hit tail would not be bit-identical — full-prompt hits restore
the original (exact-prefill) logits and stay exact.
"""

import functools
import hashlib
import threading
import time

import numpy

import jax
import jax.numpy as jnp
from jax import lax

from veles_tpu.observe.xla_stats import instrument
from veles_tpu.ops.quant import int8_cache_attend, matmul_any
from veles_tpu.parallel.transformer_step import _block_qkv, _head, _mlp

#: page 0 is the SCRATCH page: never allocated, the target of every
#: padding page-table entry and of inactive lanes' harmless appends —
#: its contents are garbage by definition and always masked.
SCRATCH_PAGE = 0


def init_paged_state(n_blocks, pages, page_size, heads, head_dim,
                     vocab, slots, dtype=jnp.float32, quantized=False,
                     mesh=None, mesh_axis="model"):
    """Pool + control state for ``slots`` concurrent sequences over
    ``pages`` pages of ``page_size`` positions (page 0 is scratch, so
    ``pages - 1`` are allocatable).

    Float tier: K/V ``(L, P, page_size, H, D)`` — the dense slab's
    layout with the slot dim replaced by pages. ``quantized=True``
    stores the int8-KV tier: head-major ``(L, P, H, D, page_size)`` q8
    with ``(L, P, H, page_size)`` f32 scales (``init_slot_state``'s
    recipe page-for-slab). ``mesh`` creates the pool in-layout: pages
    shard over their HEADS dim on ``mesh_axis`` exactly like
    ``slot_state_specs`` shards the slab, control leaves replicated."""
    from veles_tpu.parallel.decode import shard_slot_tree

    base = {
        "lengths": jnp.zeros((slots,), jnp.int32),
        "logits": jnp.zeros((slots, vocab), jnp.float32),
        "req_key": jax.random.split(jax.random.key(0), slots),
        "step": jnp.zeros((slots,), jnp.int32),
    }
    if quantized:
        qshape = (n_blocks, pages, heads, head_dim, page_size)
        sshape = (n_blocks, pages, heads, page_size)
        state = dict(base,
                     k=jnp.zeros(qshape, jnp.int8),
                     v=jnp.zeros(qshape, jnp.int8),
                     k_scale=jnp.zeros(sshape, jnp.float32),
                     v_scale=jnp.zeros(sshape, jnp.float32))
    else:
        shape = (n_blocks, pages, page_size, heads, head_dim)
        state = dict(base, k=jnp.zeros(shape, dtype),
                     v=jnp.zeros(shape, dtype))
    if mesh is not None:
        state = shard_slot_tree(
            state, mesh, paged_state_specs(quantized, axis=mesh_axis))
    return state


def paged_state_specs(quantized=False, axis="model"):
    """PartitionSpec dict for the paged state: pool pages shard over
    their HEADS dim (the slot-slab serving layout, page-for-slab),
    control leaves replicate."""
    from jax.sharding import PartitionSpec as P

    if quantized:
        kv = P(None, None, axis, None, None)    # (L, P, H, D, ps)
        scale = P(None, None, axis, None)       # (L, P, H, ps)
        extra = {"k_scale": scale, "v_scale": scale}
    else:
        kv = P(None, None, None, axis, None)    # (L, P, ps, H, D)
        extra = {}
    return dict({"k": kv, "v": kv, "lengths": P(), "logits": P(),
                 "req_key": P(), "step": P()}, **extra)


def _page_size_of(state):
    """Static page size from the pool leaf shape (minor for the int8
    head-major layout, axis 2 for float)."""
    return (state["k"].shape[-1] if "k_scale" in state
            else state["k"].shape[2])


#: the paged-state leaves whose bytes belong to the PAGE POOL rather
#: than the decoder's control state — what memscope charges the
#: ``kv_pool`` owner (observe/memscope.py)
PAGED_KV_LEAVES = ("k", "v", "k_scale", "v_scale")


def paged_kv_bytes(state):
    """Device bytes of the page arrays inside a paged decode state
    (both tiers: float K/V, or int8 K/V + f32 scales). The decoder
    stamps ``pool.page_bytes = paged_kv_bytes(state) // pool.pages``
    so attribution splits one pytree between the ``kv_pool`` and
    ``decode_state`` owners without double-counting."""
    total = 0
    for leaf in PAGED_KV_LEAVES:
        arr = state.get(leaf)
        if arr is not None:
            total += getattr(arr, "nbytes", 0) or 0
    return total


def _pad_positions(val, t_padded):
    """Zero-pad the positions axis (axis 2 of an (L, B, T, ...) stack)
    up to ``t_padded`` — whole-page scatter granularity."""
    t = val.shape[2]
    if t == t_padded:
        return val
    pad = [(0, 0)] * val.ndim
    pad[2] = (0, t_padded - t)
    return jnp.pad(val, pad)


def _scatter_pages(state, page_ids, k_all, v_all):
    """Write stacked prefill K/V (L, B, T, H, D) into the pool pages
    ``page_ids`` (B, NP) — positions padded to whole pages (stale
    padding positions are rewritten by a sequence's own appends before
    any mask exposes them, the dense engine's doctrine). Duplicate
    rows (group padding) carry equal values, so the scatter is
    well-defined. Returns the updated pool leaves as a dict."""
    n_pages = page_ids.shape[1]
    ps = _page_size_of(state)
    new = {}
    if "k_scale" in state:
        from veles_tpu.parallel.decode import _quantize_kv
        for name, val in (("k", k_all), ("v", v_all)):
            q8, scale = _quantize_kv(val)        # (L,B,T,H,D), (L,B,T,H)
            q8 = _pad_positions(q8, n_pages * ps)
            scale = _pad_positions(scale, n_pages * ps)
            lb = q8.shape[:2]
            q8 = q8.reshape(lb + (n_pages, ps) + q8.shape[3:])
            scale = scale.reshape(lb + (n_pages, ps) + scale.shape[3:])
            # pool is head-major (L,P,H,D,ps) / (L,P,H,ps)
            new[name] = state[name].at[:, page_ids].set(
                jnp.transpose(q8, (0, 1, 2, 4, 5, 3)))
            new[name + "_scale"] = state[name + "_scale"].at[
                :, page_ids].set(jnp.transpose(scale, (0, 1, 2, 4, 3)))
    else:
        for name, val in (("k", k_all), ("v", v_all)):
            val = _pad_positions(val.astype(state[name].dtype),
                                 n_pages * ps)
            lb = val.shape[:2]
            val = val.reshape(lb + (n_pages, ps) + val.shape[3:])
            new[name] = state[name].at[:, page_ids].set(val)
    return new


def _gather_block_float(state, block, page_table):
    """Float tier: (S, PB, ps, H, D) gather -> (S, PB*ps, H, D) — the
    dense ``new_k[i][:, :span]`` slice, page-addressed. Page-table rows
    list a slot's pages in logical order; padding entries point at the
    scratch page, whose garbage the mask zeroes exactly."""
    slots, pb = page_table.shape
    ps = state["k"].shape[2]
    k = state["k"][block][page_table]
    v = state["v"][block][page_table]
    shape = (slots, pb * ps) + k.shape[3:]
    return k.reshape(shape), v.reshape(shape)


def _gather_block_int8(state, block, page_table):
    """int8 tier: gathered pages re-laid head-major positions-minor —
    (S, H, D, PB*ps) q8 + (S, H, PB*ps) scales, the dequant-fused
    attend kernel's layout."""
    slots, pb = page_table.shape
    ps = state["k"].shape[-1]
    out = []
    for name in ("k", "v"):
        q8 = state[name][block][page_table]       # (S, PB, H, D, ps)
        q8 = jnp.transpose(q8, (0, 2, 3, 1, 4)).reshape(
            (slots,) + q8.shape[2:4] + (pb * ps,))
        scale = state[name + "_scale"][block][page_table]  # (S,PB,H,ps)
        scale = jnp.transpose(scale, (0, 2, 1, 3)).reshape(
            (slots, scale.shape[2], pb * ps))
        out.extend((q8, scale))
    return out


def _paged_admit_many(params, embed_table, heads, state, slots,
                      page_ids, prompt_x, req_keys, lengths):
    """Cold paged admission: the dense ``_slot_admit_many`` with the
    slab scatter replaced by a page scatter. ``page_ids`` (B, NP) maps
    each row's bucket positions onto its allocated pages; everything
    else — the shared ``_prefill_forward``, the control-row scatters,
    the duplicate-row group padding — is the dense idiom verbatim, so
    the stored K/V are bit-identical to the slab's."""
    from veles_tpu.parallel.decode import _prefill_forward

    with jax.named_scope("paged.admit"):
        logits, k_all, v_all, lengths = _prefill_forward(
            params, prompt_x, heads, lengths)
    new = dict(
        state,
        lengths=state["lengths"].at[slots].set(lengths),
        logits=state["logits"].at[slots].set(logits.astype(jnp.float32)),
        req_key=state["req_key"].at[slots].set(req_keys),
        step=state["step"].at[slots].set(jnp.zeros_like(lengths)),
    )
    new.update(_scatter_pages(state, page_ids, k_all, v_all))
    return new


def _paged_admit_tail(params, embed_table, heads, state, slots,
                      prefix_pages, tail_pages, tail_x, req_keys,
                      lengths):
    """Prefix-hit admission: prefill ONLY the unique tail. The shared
    prefix (``prefix_pages`` (B, PP) — whole pages, page-aligned) is
    gathered from the pool as attention context; the tail tokens
    (``tail_x`` (B, Tt, E), right-padded to the tail bucket) run the
    block stack with a prefix-offset causal mask and scatter their K/V
    into the fresh ``tail_pages`` (B, NT). ``lengths`` (B,) are the
    true TOTAL lengths (shared + true tail).

    Bit-identity: tail activations depend only on the prefix K/V
    (causality), the gathered pages hold the slab-exact values, and
    masked columns contribute exact zeros — so the tail's logits equal
    the dense full prefill's on CPU (the established span/bucket
    invariance idiom; float tier only — the int8-KV pool stores
    rounded K/V, so its hits are exact-prompt-only)."""
    batch, t_tail, embed = tail_x.shape
    ps = _page_size_of(state)
    shared = prefix_pages.shape[1] * ps
    # column c visible to tail query j iff c <= shared + j: the full
    # causal mask restricted to the tail rows, prefix columns first
    mask = (jnp.arange(shared + t_tail)[None, None, None, :]
            <= shared + jnp.arange(t_tail)[None, None, :, None])
    x = tail_x
    ks, vs = [], []
    with jax.named_scope("paged.admit_tail"):
        for i, blk in enumerate(params["blocks"]):
            q, k, v = _block_qkv(blk, x, heads)
            ks.append(k)
            vs.append(v)
            kp, vp = _gather_block_float(state, i, prefix_pages)
            k_cat = jnp.concatenate([kp.astype(q.dtype), k], axis=1)
            v_cat = jnp.concatenate([vp.astype(q.dtype), v], axis=1)
            # the SAME XLA attention the dense prefill's small-shape
            # path runs (ops/attention.attention), with the causal
            # mask made explicit to carry the prefix offset
            att = jax.nn.dot_product_attention(
                q, k_cat, v_cat, scale=float(1.0 / numpy.sqrt(
                    embed // heads)), mask=mask)
            x = x + matmul_any(att.reshape(batch, t_tail, embed),
                               blk["wout"]) + blk["bout"]
            x = _mlp(blk, x)
    tail_len = lengths - shared
    last = jnp.take_along_axis(
        x, jnp.maximum(tail_len - 1, 0)[:, None, None], axis=1)[:, 0]
    logits = _head(params, last)
    new = dict(
        state,
        lengths=state["lengths"].at[slots].set(lengths),
        logits=state["logits"].at[slots].set(logits.astype(jnp.float32)),
        req_key=state["req_key"].at[slots].set(req_keys),
        step=state["step"].at[slots].set(jnp.zeros_like(lengths)),
    )
    new.update(_scatter_pages(state, tail_pages, jnp.stack(ks),
                              jnp.stack(vs)))
    return new


def _paged_admit_hit(state, slots, lengths, logits, req_keys):
    """Full-prompt prefix hit: ~0 admission — the shared pages are
    already resident, so only the control rows are written. ``logits``
    (B, V) are the ORIGINAL cold prefill's last-position logits
    (cached device-side), so the first emitted token is bit-identical
    to the dense admission's."""
    with jax.named_scope("paged.admit_hit"):
        return dict(
            state,
            lengths=state["lengths"].at[slots].set(lengths),
            logits=state["logits"].at[slots].set(
                logits.astype(jnp.float32)),
            req_key=state["req_key"].at[slots].set(req_keys),
            step=state["step"].at[slots].set(jnp.zeros_like(lengths)),
        )


def _paged_slot_step(params, embed_table, heads, state, page_table,
                     active, temperature=1.0, sample=False, top_k=0,
                     use_kernel=None):
    """One decode step across all slots — the dense ``_slot_step``
    with the slab slice replaced by a page-table gather and the append
    target routed through the table. ``page_table`` (S, PB) int32 lists
    each slot's live pages in logical order (padding/retired rows point
    at scratch); the attended span is ``PB * page_size`` — the host
    sizes PB to the longest live sequence plus the dispatch's appends,
    so per-step cost scales with live tokens, one compiled program per
    PB (the pages-per-slot bucket).

    Two attend formulations behind ONE jitted signature: the portable
    page-table GATHER (the CPU bit-identity reference), or — when
    ``ops/paged_attention.use_paged_kernel()`` says so — the fused
    Pallas kernel that walks the table directly and attends only each
    slot's LIVE pages (span/page overshoot deleted at the kernel
    level). ``use_kernel=None`` reads the probe at TRACE time, so the
    ``paged.step`` / ``paged.dispatch`` instrument names and the AOT
    facade are identical either way; flipping the probe does not
    invalidate already-traced programs (tests ``jax.clear_caches()``
    around it). :func:`sharded_paged_fns` pins it False — the mesh
    tier takes the gather by rule (``use_paged_kernel(mesh)``)."""
    from veles_tpu.ops import paged_attention as pgatt
    from veles_tpu.parallel.decode import (_cache_attend, _pick_token,
                                           _positions_last)

    slots = state["lengths"].shape[0]
    quantized = "k_scale" in state
    ps = _page_size_of(state)
    pb = page_table.shape[1]
    span = pb * ps
    if use_kernel is None:
        use_kernel = pgatt.use_paged_kernel()
    lengths = state["lengths"]
    if sample:
        step_keys = jax.vmap(jax.random.fold_in)(state["req_key"],
                                                 state["step"])
        tok_in = jax.vmap(
            lambda l, k: _pick_token(l[None], k, temperature, True,
                                     top_k)[0])(state["logits"],
                                                step_keys)
    else:
        tok_in = jnp.argmax(state["logits"], axis=-1)
    x = embed_table[tok_in][:, None, :]
    embed = x.shape[-1]
    visible = jnp.arange(span)[None, :] <= lengths[:, None]
    if quantized:
        mask_addend = jnp.where(visible, 0.0, -1e30).astype(jnp.float32)
        # python float (weak type): `q * inv_sqrt` must NOT promote a
        # bf16 q to f32 (see decode.decode_step)
        inv_sqrt = (embed // heads) ** -0.5
    else:
        mask = visible[:, None, None, :]
    new_k, new_v = state["k"], state["v"]
    new_ks = state.get("k_scale")
    new_vs = state.get("v_scale")
    from veles_tpu.parallel.decode import _quantize_kv
    for i, blk in enumerate(params["blocks"]):
        q, k, v = _block_qkv(blk, x, heads)
        # per-slot append through the page table: position p lives in
        # the slot's logical page p // ps at offset p % ps. Unrolled
        # dynamic_update_slice per slot, NOT one scatter (the dense
        # engine's measured XLA-on-TPU preference). Tail pages are
        # slot-private by construction (shared prefix pages are never
        # an append target — docs/paged_kv.md), and a retired lane's
        # clamped/zero table row routes its harmless write to scratch.
        if quantized:
            kq, ks = _quantize_kv(k)         # (S,1,H,D), (S,1,H)
            vq, vs = _quantize_kv(v)
            for s in range(slots):
                pos = lengths[s]
                page = page_table[s, jnp.minimum(pos // ps, pb - 1)]
                off = pos % ps
                new_k = lax.dynamic_update_slice(
                    new_k, jnp.transpose(kq[s:s + 1], (0, 2, 3, 1))[None],
                    (i, page, 0, 0, off))
                new_v = lax.dynamic_update_slice(
                    new_v, jnp.transpose(vq[s:s + 1], (0, 2, 3, 1))[None],
                    (i, page, 0, 0, off))
                new_ks = lax.dynamic_update_slice(
                    new_ks, jnp.transpose(ks[s:s + 1], (0, 2, 1))[None],
                    (i, page, 0, off))
                new_vs = lax.dynamic_update_slice(
                    new_vs, jnp.transpose(vs[s:s + 1], (0, 2, 1))[None],
                    (i, page, 0, off))
            if use_kernel:
                att = pgatt.paged_attend_int8(
                    (q * inv_sqrt)[:, 0], new_k[i], new_ks[i],
                    new_v[i], new_vs[i], page_table, lengths,
                    page_size=ps)[:, None]
            else:
                pool = dict(state, k=new_k, v=new_v, k_scale=new_ks,
                            v_scale=new_vs)
                k8, kscale, v8, vscale = _gather_block_int8(pool, i,
                                                            page_table)
                att = int8_cache_attend(q * inv_sqrt, k8, kscale, v8,
                                        vscale, mask_addend)
        else:
            for s in range(slots):
                pos = lengths[s]
                page = page_table[s, jnp.minimum(pos // ps, pb - 1)]
                off = pos % ps
                new_k = lax.dynamic_update_slice(
                    new_k, k[s:s + 1][None].astype(new_k.dtype),
                    (i, page, off, 0, 0))
                new_v = lax.dynamic_update_slice(
                    new_v, v[s:s + 1][None].astype(new_v.dtype),
                    (i, page, off, 0, 0))
            if use_kernel:
                att = pgatt.paged_attend(
                    q[:, 0], new_k[i], new_v[i], page_table, lengths,
                    page_size=ps)[:, None]
            else:
                pool = dict(state, k=new_k, v=new_v)
                k_g, v_g = _gather_block_float(pool, i, page_table)
                # the pool stays positions-major: a transposed view,
                # which XLA folds into the attend's dots
                att = _cache_attend(q, _positions_last(k_g),
                                    _positions_last(v_g), mask)
        att = att.astype(x.dtype)
        x = x + matmul_any(att.reshape(slots, 1, embed),
                           blk["wout"]) + blk["bout"]
        x = _mlp(blk, x)
    logits = _head(params, x[:, 0]).astype(jnp.float32)
    new_state = dict(
        state, k=new_k, v=new_v,
        lengths=jnp.where(active, lengths + 1, lengths),
        logits=jnp.where(active[:, None], logits, state["logits"]),
        step=jnp.where(active, state["step"] + 1, state["step"]),
    )
    if quantized:
        new_state["k_scale"] = new_ks
        new_state["v_scale"] = new_vs
    return new_state, tok_in


def _paged_slot_step_many(params, embed_table, heads, state, page_table,
                          active, n, temperature=1.0, sample=False,
                          top_k=0, use_kernel=None):
    """``n`` lockstep paged steps as ONE ``lax.scan`` dispatch. The
    page table is constant across the chunk — the host pre-maps every
    page the chunk's appends can touch (``PB * page_size`` covers the
    longest live sequence plus the whole chunk), so mid-chunk page
    boundary crossings route through the same table."""
    def body(state, _):
        state, emitted = _paged_slot_step(
            params, embed_table, heads, state, page_table, active,
            temperature, sample, top_k, use_kernel)
        return state, emitted

    with jax.named_scope("paged.dispatch"):
        return lax.scan(body, state, None, length=n)


def _paged_restore(state, page_ids, values):
    """Rebuild path: scatter preserved page payloads (one stacked
    array per pool leaf, (L, NP, ...page shape)) back into a FRESH
    pool at the re-allocated ``page_ids`` (NP,) — restoring the prefix
    cache across a breaker rebuild is a copy, never a re-prefill."""
    with jax.named_scope("paged.restore"):
        new = dict(state)
        for name, val in values.items():
            new[name] = state[name].at[:, page_ids].set(
                val.astype(state[name].dtype))
        return new


# -- the jitted single-chip surface -----------------------------------------
# One compiled program per (bucket, group, pages bucket) via the jit
# cache; instrument() books compiles/hits per name so the dispatch-count
# and recompile-storm CI hooks extend to the paged engine unchanged.

paged_admit_many = instrument("paged.admit", functools.partial(
    jax.jit, static_argnames=("heads",),
    donate_argnames=("state",))(_paged_admit_many))
paged_admit_tail = instrument("paged.admit_tail", functools.partial(
    jax.jit, static_argnames=("heads",),
    donate_argnames=("state",))(_paged_admit_tail))
paged_admit_hit = instrument("paged.admit_hit", functools.partial(
    jax.jit, donate_argnames=("state",))(_paged_admit_hit))
paged_slot_step = instrument("paged.step", functools.partial(
    jax.jit, static_argnames=("heads", "sample", "top_k"),
    donate_argnames=("state",))(_paged_slot_step))
paged_slot_step_many = instrument("paged.dispatch", functools.partial(
    jax.jit, static_argnames=("heads", "n", "sample", "top_k"),
    donate_argnames=("state",))(_paged_slot_step_many))
paged_restore = instrument("paged.restore", functools.partial(
    jax.jit, donate_argnames=("state",))(_paged_restore))


#: (mesh, axis, quantized) -> pinned jit objects, same doctrine as
#: decode._SLOT_FNS: output shardings pinned to the canonical
#: layout so a donated state never drifts and defeats the jit cache;
#: check-then-insert locked so racing builders share one jit object.
_SHARDED_PAGED_FNS = {}
_SHARDED_PAGED_LOCK = threading.Lock()


def sharded_paged_fns(mesh, mesh_axis="model", quantized=False):
    """The sharded paged engine's jitted call surface: the SAME raw
    functions as the single-chip programs (one copy of the math — the
    bit-identity contract), jitted per layout with the state outputs
    pinned to :func:`paged_state_specs` and small operands replicated.
    Returns ``(admit, admit_tail, admit_hit, step, step_many,
    restore)``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    key = (mesh, mesh_axis, bool(quantized))
    with _SHARDED_PAGED_LOCK:
        fns = _SHARDED_PAGED_FNS.get(key)
    if fns is not None:
        return fns
    state_sh = {
        name: NamedSharding(mesh, spec)
        for name, spec in paged_state_specs(quantized,
                                            axis=mesh_axis).items()}
    replicated = NamedSharding(mesh, P())
    admit = instrument("paged.admit", jax.jit(
        _paged_admit_many, static_argnames=("heads",),
        donate_argnames=("state",), out_shardings=state_sh))
    admit_tail = instrument("paged.admit_tail", jax.jit(
        _paged_admit_tail, static_argnames=("heads",),
        donate_argnames=("state",), out_shardings=state_sh))
    admit_hit = instrument("paged.admit_hit", jax.jit(
        _paged_admit_hit, donate_argnames=("state",),
        out_shardings=state_sh))
    # the mesh tier takes the gather by rule: GSPMD cannot partition a
    # bare pallas_call over the head-sharded pool
    # (ops/paged_attention.use_paged_kernel)
    step = instrument("paged.step", jax.jit(
        functools.partial(_paged_slot_step, use_kernel=False),
        static_argnames=("heads", "sample", "top_k"),
        donate_argnames=("state",),
        out_shardings=(state_sh, replicated)))
    step_many = instrument("paged.dispatch", jax.jit(
        functools.partial(_paged_slot_step_many, use_kernel=False),
        static_argnames=("heads", "n", "sample", "top_k"),
        donate_argnames=("state",),
        out_shardings=(state_sh, replicated)))
    restore = instrument("paged.restore", jax.jit(
        _paged_restore, donate_argnames=("state",),
        out_shardings=state_sh))
    fns = (admit, admit_tail, admit_hit, step, step_many, restore)
    with _SHARDED_PAGED_LOCK:
        fns = _SHARDED_PAGED_FNS.setdefault(key, fns)
    return fns


# -- host side ---------------------------------------------------------------

def _prefix_key(tokens):
    """Stable content hash of a token prefix (collisions are guarded
    by an exact token comparison on lookup)."""
    return hashlib.sha1(
        numpy.ascontiguousarray(tokens, numpy.int32).tobytes()
    ).hexdigest()


def _boundary_keys(tokens, page_size, whole):
    """Prefix keys of every whole-page boundary (``tokens[:k*ps]`` for
    k=1..whole) in one O(T) pass: a single incremental SHA-1 advanced
    page by page and copied at each boundary. Hashing each boundary
    from scratch is O(T^2/page_size) bytes per admission — quadratic
    in the prompt; the digests are byte-identical to
    :func:`_prefix_key` of the same prefix."""
    data = numpy.ascontiguousarray(tokens, numpy.int32)
    hasher = hashlib.sha1()
    keys = []
    for k in range(whole):
        hasher.update(data[k * page_size:(k + 1) * page_size]
                      .tobytes())
        keys.append(hasher.copy().hexdigest())
    return keys


class PrefixCache:
    """Refcount-backed LRU cache of page-granular token prefixes.

    Lives OUTSIDE the device state so a breaker rebuild can carry it
    across decoders: each entry holds the prefix tokens, the page ids
    (re-mapped on restore), the original cold prefill's last-position
    logits (full-prompt hits admit with zero prefill), and a
    device-array shadow of each page's payload for the restore scatter.
    Counters are cumulative across rebuilds (the Prometheus contract).
    """

    def __init__(self, max_entries=256):
        import collections

        self.max_entries = int(max_entries)
        self.entries = collections.OrderedDict()   # key -> entry
        self.page_shadow = {}                      # page id -> {leaf: arr}
        self.counters = {"hits": 0, "misses": 0, "evictions": 0}

    def __len__(self):
        return len(self.entries)


class PagePool:
    """Host-side page table: free list, per-page refcounts, the prefix
    cache, admission reservations and the page-release-rate window.

    Thread model: the decoder driver thread owns admissions/frees; the
    HTTP admission gate reserves from handler threads — every mutation
    takes the one RLock. Refcounts: a live slot holds one ref per
    mapped page; each prefix-cache entry holds one ref per page it
    names (nested boundary entries stack refs naturally). A page frees
    when its count reaches zero; cache entries are evicted LRU-first
    when an allocation runs short."""

    def __init__(self, pages, page_size, cache=None):
        import collections

        if pages < 2:
            raise ValueError("pool needs >= 2 pages (page 0 is "
                             "scratch), got %d" % pages)
        if page_size < 1:
            raise ValueError("page_size must be >= 1, got %d"
                             % page_size)
        self.pages = int(pages)
        self.page_size = int(page_size)
        self._lock = threading.RLock()
        self._free = list(range(self.pages - 1, SCRATCH_PAGE, -1))
        self._refs = {}
        self._reserved = 0
        #: (monotonic stamp, pages freed) — the observed release rate
        #: that prices Retry-After for pool-aware backpressure
        self._freed_events = collections.deque(maxlen=512)
        self.cache = cache if cache is not None else PrefixCache()
        #: device bytes per page across every KV leaf — the decoder
        #: stamps this once from its paged state (the page ARRAYS live
        #: in the decode state pytree; the pool only owns the table),
        #: so memscope attribution can charge the pool its footprint
        #: without double-counting the state tree
        self.page_bytes = 0

    # -- accounting -------------------------------------------------------
    @property
    def capacity(self):
        """Allocatable pages (scratch excluded)."""
        return self.pages - 1

    @property
    def free_pages(self):
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self):
        with self._lock:
            return self.capacity - len(self._free)

    def hbm_bytes(self):
        """Device footprint of the page arrays this pool tables:
        pages x page_bytes. Lock-free (two write-once ints) — this is
        a memscope accountant and runs at metrics scrape time."""
        return self.pages * self.page_bytes

    def shadow_bytes(self):
        """Host bytes pinned by the prefix cache's page shadows (the
        re-materialization copies that survive a breaker rebuild).
        Iterates a point-in-time list copy without the lock — an
        approximate byte count is fine for attribution, and a memscope
        accountant must never contend with the admission path."""
        total = 0
        for leaves in list(self.cache.page_shadow.values()):
            for arr in list(leaves.values()):
                total += getattr(arr, "nbytes", 0) or 0
        return total

    def snapshot(self):
        with self._lock:
            counters = dict(self.cache.counters)
            hits = counters.get("hits", 0)
            misses = counters.get("misses", 0)
            return {
                "pages_total": self.capacity,
                "pages_used": self.capacity - len(self._free),
                "pages_free": len(self._free),
                "page_size": self.page_size,
                "reserved_pages": self._reserved,
                "prefix_entries": len(self.cache),
                "prefix_hits": hits,
                "prefix_misses": misses,
                "prefix_evictions": counters.get("evictions", 0),
                "prefix_hit_rate": (round(hits / (hits + misses), 4)
                                    if hits + misses else None),
            }

    # -- alloc / free -----------------------------------------------------
    def alloc(self, n):
        """Allocate ``n`` pages (refcount 1 each), evicting LRU prefix
        entries under pressure; returns the page ids or ``None`` when
        the pool cannot satisfy the request even after eviction."""
        if n <= 0:
            return []
        with self._lock:
            while len(self._free) < n and self._evict_lru():
                pass
            if len(self._free) < n:
                return None
            pages = [self._free.pop() for _ in range(n)]
            for page in pages:
                self._refs[page] = 1
            return pages

    def retain(self, pages):
        """Add one ref per page (a slot mapping shared prefix pages)."""
        with self._lock:
            for page in pages:
                self._refs[page] += 1

    def release(self, pages):
        """Drop one ref per page; refcount-0 pages return to the free
        list (and feed the release-rate window)."""
        freed = 0
        with self._lock:
            for page in pages:
                refs = self._refs.get(page)
                if refs is None:
                    continue
                if refs <= 1:
                    del self._refs[page]
                    self._free.append(page)
                    self.cache.page_shadow.pop(page, None)
                    freed += 1
                else:
                    self._refs[page] = refs - 1
            if freed:
                self._freed_events.append((time.monotonic(), freed))

    def _evict_lru(self):
        """Drop the least-recently-used prefix entry; True when one
        was evicted (its refs released — pages used by live slots stay
        resident until those slots retire)."""
        cache = self.cache
        if not cache.entries:
            return False
        key, entry = next(iter(cache.entries.items()))
        del cache.entries[key]
        cache.counters["evictions"] += 1
        self.release(entry["pages"])
        return True

    def flush_prefix_cache(self):
        """Drop EVERY prefix entry (and its page refs) — the weight
        hot-swap seam (docs/zero_downtime.md): cached pages hold KV
        bytes and logits computed under the OLD weights, so one
        reused prefix after a swap would splice stale activations
        into new-weight streams. Pages still mapped by live slots
        stay resident until those slots retire (they finish on the
        old weights by the drain contract). Returns the number of
        entries dropped."""
        dropped = 0
        with self._lock:
            while self._evict_lru():
                dropped += 1
            self.cache.page_shadow.clear()
        return dropped

    # -- admission reservations (pool-aware backpressure) -----------------
    def try_reserve(self, n):
        """Reserve worst-case page demand for one admission: the sum of
        live reservations never exceeds capacity, so an admitted
        request can always be satisfied (prefix sharing and eviction
        only ever FREE pages relative to the worst case) — the
        no-deadlock invariant ``ServingHealth.try_admit`` gates on."""
        with self._lock:
            if self._reserved + n > self.capacity:
                return False
            self._reserved += n
            return True

    def unreserve(self, n):
        with self._lock:
            self._reserved = max(0, self._reserved - n)

    def release_rate(self, window=60.0):
        """Observed page releases per second over the trailing window
        (0.0 when nothing freed yet)."""
        now = time.monotonic()
        with self._lock:
            events = [(t, n) for t, n in self._freed_events
                      if now - t <= window]
        if not events:
            return 0.0
        span = max(now - events[0][0], 1e-3)
        return sum(n for _, n in events) / span

    def retry_after(self, need, fallback=1.0):
        """Honest Retry-After for a pool rejection: how long the
        observed release rate needs to free ``need`` pages, clamped to
        [1, 60] seconds; the fallback covers a cold window."""
        rate = self.release_rate()
        if rate <= 0:
            return max(1.0, float(fallback))
        return float(min(60.0, max(1.0, need / rate)))

    # -- prefix cache -----------------------------------------------------
    def lookup(self, tokens, allow_partial=True):
        """Longest page-granular cached prefix of ``tokens``; returns
        ``(entry, shared_len)`` with the shared pages RETAINED for the
        caller's slot, or ``(None, 0)`` on a miss. A full-prompt match
        requires stored logits (otherwise the last page is treated as
        tail so the admission can recompute them); ``allow_partial=
        False`` (the int8-KV tier) accepts exact-prompt hits only.

        NO counters move here: the caller books :meth:`book_hit` /
        :meth:`book_miss` once the admission commits, so a hit rolled
        back by :meth:`unlookup` (no pages for the tail) or a blocked
        request re-scanned every driver pass never skews the
        exported-monotone ``veles_prefix_cache_*_total`` counters."""
        ps = self.page_size
        tokens = numpy.asarray(tokens, numpy.int32)
        n = len(tokens)
        # boundary keys hashed OUTSIDE the lock (one O(T) incremental
        # pass): the HTTP gate's try_reserve shares this lock
        keys = _boundary_keys(tokens, ps, n // ps)
        with self._lock:
            for k in range(n // ps, 0, -1):
                shared = k * ps
                if shared == n:
                    pass          # full hit: needs stored logits
                elif not allow_partial:
                    continue
                key = keys[k - 1]
                entry = self.cache.entries.get(key)
                if entry is None:
                    continue
                if not numpy.array_equal(entry["tokens"],
                                         tokens[:shared]):
                    continue      # hash collision: not a match
                if shared == n and entry["logits"] is None:
                    continue
                self.cache.entries.move_to_end(key)
                self.retain(entry["pages"])
                return entry, shared
            return None, 0

    def book_hit(self):
        """Count one prefix-cache hit — called by the admission path
        AFTER the hit commits (slot taken, tail pages allocated), never
        at lookup time, so the counter stays monotone under rollback."""
        with self._lock:
            self.cache.counters["hits"] += 1

    def book_miss(self):
        """Count one prefix-cache miss — like :meth:`book_hit`, booked
        when the COLD admission commits, not at lookup time: a pool-
        blocked request re-scanned at the queue front every driver pass
        must not inflate ``veles_prefix_cache_misses_total`` (and
        crater the hit rate) while it waits."""
        with self._lock:
            self.cache.counters["misses"] += 1

    def unlookup(self, entry):
        """Roll a :meth:`lookup` hit back (the caller could not admit
        — e.g. no pages for the tail): drop the retained refs. The hit
        was never booked (:meth:`book_hit` runs only on commit), so a
        retried admission still books exactly once."""
        with self._lock:
            self.release(entry["pages"])

    def insert(self, tokens, pages, state, logits=None):
        """Publish an admission's full pages into the cache: one
        entry per page boundary (``tokens[:k*ps]`` for every whole
        page k), each holding refs on its pages, with the prefill
        logits attached to the exact-length boundary. Pure host
        bookkeeping — page payload shadows are captured lazily at
        breaker-trip time (:meth:`capture_shadows`), never on the
        admission hot path."""
        ps = self.page_size
        tokens = numpy.asarray(tokens, numpy.int32)
        whole = len(tokens) // ps
        if whole == 0:
            return
        keys = _boundary_keys(tokens, ps, whole)  # outside the lock
        with self._lock:
            for k in range(1, whole + 1):
                shared = k * ps
                key = keys[k - 1]
                entry = self.cache.entries.get(key)
                boundary_logits = (logits if shared == len(tokens)
                                   else None)
                if entry is not None:
                    self.cache.entries.move_to_end(key)
                    if entry["logits"] is None \
                            and boundary_logits is not None:
                        entry["logits"] = boundary_logits
                    continue
                entry_pages = list(pages[:k])
                self.retain(entry_pages)
                self.cache.entries[key] = {
                    "tokens": tokens[:shared].copy(),
                    "pages": entry_pages,
                    "length": shared,
                    "logits": boundary_logits,
                }
            while len(self.cache.entries) > self.cache.max_entries:
                self._evict_lru()

    def capture_shadows(self, state):
        """Copy every cached-but-unshadowed page's payload to host —
        the rebuild-adoption prelude (``GenerateAPI._rebuild`` runs it
        on the dying decoder), NOT the admission hot path: cached
        pages are read-only by construction (appends land past the
        prompt, divergence allocates fresh pages), so the bytes
        captured at trip time equal the bytes at publication — and
        cold admissions never pay the per-page device sync + D2H
        transfer that each :func:`_shadow_page` blocks on."""
        with self._lock:
            named = {page for entry in self.cache.entries.values()
                     for page in entry["pages"]}
            missing = [page for page in named
                       if page not in self.cache.page_shadow]
        # D2H outside the lock: entry refs pin the pages, and the HTTP
        # pool gate must not stall on the transfer
        shadows = {page: _shadow_page(state, page) for page in missing}
        with self._lock:
            still = {page for entry in self.cache.entries.values()
                     for page in entry["pages"]}
            for page, shadow in shadows.items():
                # a page evicted (freed) during the copy may already be
                # recycled under a NEW prefix — a stale shadow for it
                # would restore wrong bytes
                if page in still:
                    self.cache.page_shadow.setdefault(page, shadow)

    def restore_entries(self, state, restore_fn):
        """Adopt a previous decoder's prefix cache into THIS (fresh)
        pool: allocate new pages for the union of cached pages, scatter
        the shadowed payloads back with ``restore_fn(state, page_ids,
        values) -> state``, and re-point every entry. Entries whose
        shadow is gone (or that no longer fit) are dropped. Returns the
        updated device state."""
        cache = self.cache
        with self._lock:
            old_pages = []
            for entry in cache.entries.values():
                for page in entry["pages"]:
                    if page not in old_pages:
                        old_pages.append(page)
            old_pages = [p for p in old_pages if p in cache.page_shadow]
            # drop entries referencing unshadowed pages outright
            # (capture_shadows failed or never ran for them) — counted
            # as evictions like every other path that loses an entry
            for key in [k for k, e in cache.entries.items()
                        if any(p not in cache.page_shadow
                               for p in e["pages"])]:
                del cache.entries[key]
                cache.counters["evictions"] += 1
            shadow = dict(cache.page_shadow)
            cache.page_shadow = {}
            # oldest entries drop first when the fresh pool is smaller.
            # Sized against the FREE LIST directly: alloc()'s own LRU
            # eviction cannot help here — the surviving entries name
            # OLD-pool page ids, so evicting them frees nothing in
            # this pool.
            while old_pages and len(self._free) < len(old_pages):
                cache.entries.popitem(last=False)
                # rebuild-pressure drops ARE evictions: an operator
                # watching veles_prefix_cache_evictions_total after a
                # breaker trip must see entries leave, not just the
                # entries gauge fall
                cache.counters["evictions"] += 1
                still = set()
                for entry in cache.entries.values():
                    still.update(entry["pages"])
                old_pages = [p for p in old_pages if p in still]
            if not old_pages:
                cache.entries.clear()
                return state
            new_ids = self.alloc(len(old_pages))
            mapping = dict(zip(old_pages, new_ids))
            for old, new in mapping.items():
                cache.page_shadow[new] = shadow[old]
            # entry refs: alloc gave each new page one ref; add the
            # remaining (entries-per-page - 1) refs
            counts = {}
            for entry in cache.entries.values():
                entry["pages"] = [mapping[p] for p in entry["pages"]]
                for page in entry["pages"]:
                    counts[page] = counts.get(page, 0) + 1
            for page, count in counts.items():
                if count > 1:
                    self.retain([page] * (count - 1))
            # pages shadowed but no longer named by any entry (their
            # entries were dropped above for referencing some OTHER
            # unshadowed page): freed, unshadowed, and excluded from
            # the scatter — restoring them would KeyError on the
            # popped shadow
            orphan = [p for p in new_ids if p not in counts]
            if orphan:
                self.release(orphan)
                for page in orphan:
                    self.cache.page_shadow.pop(page, None)
                new_ids = [p for p in new_ids if p in counts]
        page_ids = jnp.asarray(new_ids, jnp.int32)
        values = _stack_shadow(self.cache.page_shadow, new_ids)
        if values:
            state = restore_fn(state, page_ids, values)
        return state


def _shadow_page(state, page):
    """HOST copies of one page's payload across every pool leaf —
    they survive the pool's donation (rebuild restores them with a
    scatter, never a re-prefill) without doubling the cached pages'
    HBM; the device round-trip only happens on the rare rebuild."""
    return {name: numpy.asarray(state[name][:, page])
            for name in ("k", "v", "k_scale", "v_scale")
            if name in state}


def _stack_shadow(page_shadow, page_ids):
    """Stack per-page shadows into one (L, NP, ...) host array per
    leaf for the restore scatter."""
    if not page_ids:
        return {}
    leaves = page_shadow[page_ids[0]].keys()
    return {name: numpy.stack([page_shadow[p][name] for p in page_ids],
                              axis=1)
            for name in leaves}


def pages_for(positions, page_size):
    """Pages needed to hold ``positions`` tokens (>= 1)."""
    return max(1, -(-int(positions) // int(page_size)))


def default_pool_pages(slots, max_len, page_size, chunk=1):
    """Slab-equivalent pool size: every slot full to ``max_len`` plus
    the dispatch overshoot for chunks up to ``chunk``, plus the
    scratch page — the one formula the decoder default,
    ``init_slot_state`` and the bench all share, so 'same HBM as the
    dense slab' means the same thing everywhere.

    The overshoot term is load-bearing: ``dispatch_chunk`` advances
    lanes past retirement and pre-maps ``slot_len + chunk`` positions
    before every dispatch, so under the lag-1 pipeline a slot legally
    running ``prompt + budget == max_len`` demands pages for up to
    ``max_len - 1 + 2 * chunk`` positions near the end of its decode.
    The dense slab absorbs that with a clamped ``dynamic_update_slice``;
    a pool sized without the slack raises mid-decode on workloads the
    slab serves."""
    return int(slots) * pages_for(int(max_len) + 2 * int(chunk),
                                  page_size) + 1
