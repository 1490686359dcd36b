"""KV-cache autoregressive decoding for the causal-LM tier.

Training-side long context is covered by ring/Ulysses sequence
parallelism (``transformer_step.py``); this module is the SERVING side:
generate tokens from the same pre-LN causal model without recomputing
the prompt every step. TPU-native shape: the whole generation loop is
ONE ``lax.scan`` inside one jit — per-step K/V appends are
``lax.dynamic_update_slice`` into a static-shape cache (XLA keeps it
in-place via donation), the attention against the cache prefix masks by
position, and the sampled token feeds back through the scan carry. No
reference counterpart (VELES predates transformers) — additive tier.

Numerical contract: decode produces the same logits as running
``transformer_step._forward`` over the growing full sequence to within
fp-reassociation tolerance (``tests/test_decode.py`` asserts
rtol 2e-4 — the cached path computes attention in a different order
and ``_forward``'s core may take the engine's reduced-precision
policy, so equality is numerical, not bitwise), because both use the
identical parameter pytree and sublayer math.
"""

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
from jax import lax

from veles_tpu.ops.quant import (int8_cache_attend, matmul_any,
                                 quantize_int8)
from veles_tpu.observe.xla_stats import instrument
from veles_tpu.ops import slab_write
from veles_tpu.parallel import blocks
# ONE copy of the sublayer math, shared with the training-side full
# forward — the equivalence the module contract promises is structural.
# The slot engine runs whatever block the parameters declare through
# the seam (parallel/blocks.py); ``generate`` and the tensor-parallel
# decode run GPT-2's block through its helpers directly.
from veles_tpu.parallel.blocks import (  # noqa: F401  (their old home)
    _block_qkv, _cache_attend, _head, _mlp, _positions_last, _quantize_kv)
from veles_tpu.parallel.mesh import shard_map


def init_kv_cache(n_blocks, batch, max_len, heads, head_dim,
                  dtype=jnp.float32, quantized=False):
    """Static-shape cache: K/V per block, plus the filled length.

    ``quantized=True`` stores K/V as int8 with one f32 absmax scale per
    (block, batch, position, head) — the KV half of the int8 serving
    tier. At decode lengths the cache read rivals the weight read, so
    this halves the OTHER half of the memory-bound loop's traffic.
    Layout is (L, B, H, D, T) — head-major, positions minor: the
    dequant-fused attend's dots then tile the MXU natively
    (q x K contracts D with T on lanes; V x p contracts T), and XLA
    cannot sneak a materialized bf16 widening of the cache in between
    (measured 4-8x slower in every positions-major layout)."""
    shape = (n_blocks, batch, max_len, heads, head_dim)
    if quantized:
        qshape = (n_blocks, batch, heads, head_dim, max_len)
        sshape = (n_blocks, batch, heads, max_len)
        return {"k": jnp.zeros(qshape, jnp.int8),
                "v": jnp.zeros(qshape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32),
                "length": jnp.zeros((), jnp.int32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "length": jnp.zeros((), jnp.int32)}


def prefill_parts(arch, batch, t):
    """In how many parts a group of ``batch`` prompts of ``t``
    positions goes through a block (``Arch.prefill_tokens``): a
    divisor of ``batch``, 1 for the whole group at once."""
    parts = 1
    if arch.prefill_tokens:
        parts = max(1, batch * t // arch.prefill_tokens)
        while batch % parts:
            parts -= 1
    return parts


def admit_rows(arch, t):
    """The most prompts of ``t`` positions one admission takes
    (``Arch.admit_tokens``); None: every prompt of the bucket waiting."""
    if not arch.admit_tokens:
        return None
    return max(1, arch.admit_tokens // t)


def _prompt_forward(params, x, heads, length=None, embed_table=None):
    """The prompt forward pass shared by every prefill surface: run
    ``x`` (B, T, E) through all blocks once and return ``(last_logits,
    rows, cache_len)``: ``rows`` per block what the cache keeps of the
    prompt, as the block's kind makes it (``project``, ``keep``: a
    row a position, or the fixed state after each row's true length;
    the caller decides how to store it). ``embed_table`` is the head
    of a model that ties the two.

    ``length`` may be ``None`` (use T), a traced scalar (one shared
    right-padded length), or a traced (B,) vector (per-row true lengths
    — the batched same-bucket admission path); the logits always read
    from each row's position ``length - 1``. Where the architecture
    says so (``prefill_tokens``), the rows of a large group go through
    a block in turn, so that its temporaries stay those of a part."""
    batch, t, _ = x.shape
    arch = blocks.arch_of(params)
    cache_len = jnp.int32(t) if length is None \
        else jnp.asarray(length, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t), (batch, t))
    live = positions < jnp.reshape(cache_len, (-1, 1))
    parts = prefill_parts(arch, batch, t)
    rows_all = []
    for blk, kind in zip(params["blocks"], blocks.block_kinds(
            arch, len(params["blocks"]))):
        if parts == 1:
            x, rows = blocks.block_forward(arch, blk, x, heads,
                                           positions, live, kind)
        else:
            def apart(a):
                return a.reshape((parts, batch // parts) + a.shape[1:])

            x, rows = lax.map(
                lambda part, blk=blk, kind=kind: blocks.block_forward(
                    arch, blk, part[0], heads, part[1], part[2], kind),
                (apart(x), apart(positions), apart(live)))
            x, rows = jax.tree.map(
                lambda a: a.reshape((batch,) + a.shape[2:]), (x, rows))
        rows_all.append(rows)
    if length is None:
        last = x[:, -1]
    elif cache_len.ndim == 0:
        last = lax.dynamic_slice_in_dim(x, cache_len - 1, 1, axis=1)[:, 0]
    else:
        last = jnp.take_along_axis(
            x, (cache_len - 1)[:, None, None], axis=1)[:, 0]
    return blocks.head(arch, params, last, embed_table), rows_all, \
        cache_len


def _prefill_forward(params, x, heads, length=None):
    """:func:`_prompt_forward` for the callers that store GPT-2's K/V
    themselves (``prefill``, the paged pool): ``(last_logits, k_all,
    v_all, cache_len)`` with ``k_all``/``v_all`` stacked
    (L, B, T, H, D)."""
    blocks.require_gpt2(params, "a cache of k/v leaves", tier="paged")
    logits, rows, cache_len = _prompt_forward(params, x, heads, length)
    return (logits, jnp.stack([r["k"] for r in rows]),
            jnp.stack([r["v"] for r in rows]), cache_len)


def prefill(params, x, heads, cache, length=None):
    """Run the prompt (B, T, E) once, filling ``cache`` positions
    [0, T); returns ``(last_logits, cache)`` with ``last_logits``
    (B, vocab) for the first generated token.

    ``length`` (traced scalar, default T) supports right-PADDED
    prompts: the causal mask means pad positions past ``length`` never
    influence the real positions' K/V, the logits read from position
    ``length - 1``, and the cache length is ``length`` — so one
    compiled program serves a whole bucket of prompt lengths (the
    continuous-batching admission path)."""
    logits, k_all, v_all, cache_len = _prefill_forward(params, x, heads,
                                                       length)
    new = {"length": cache_len}
    if "k_scale" in cache:
        for name, val in (("k", k_all), ("v", v_all)):
            q8, scale = _quantize_kv(val)        # (L,B,T,H,D),(L,B,T,H)
            # head-major, positions-minor cache layout (see
            # init_kv_cache): (L,B,H,D,T) / (L,B,H,T)
            new[name] = lax.dynamic_update_slice(
                cache[name], jnp.transpose(q8, (0, 1, 3, 4, 2)),
                (0, 0, 0, 0, 0))
            new[name + "_scale"] = lax.dynamic_update_slice(
                cache[name + "_scale"],
                jnp.transpose(scale, (0, 1, 3, 2)), (0, 0, 0, 0))
    else:
        new["k"] = lax.dynamic_update_slice(
            cache["k"], k_all.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        new["v"] = lax.dynamic_update_slice(
            cache["v"], v_all.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
    return logits, new


def decode_step(params, x_tok, heads, cache):
    """One token (B, 1, E) through every block against the cache;
    returns ``(logits, cache)`` with the token's K/V appended."""
    batch, _, embed = x_tok.shape
    length = cache["length"]
    quantized = "k_scale" in cache
    # positions [0, length] are valid (the new token attends to itself)
    if quantized:
        max_len = cache["k"].shape[-1]  # head-major layout: T is minor
        mask_addend = jnp.where(jnp.arange(max_len) <= length, 0.0,
                                -1e30).astype(jnp.float32)
        # python float (weak type): `q * inv_sqrt` must NOT promote a
        # bf16 q to f32 — that would kill the fallback path's bf16
        # compute branch and widen the int8 cache to f32
        inv_sqrt = (embed // heads) ** -0.5
    else:
        max_len = cache["k"].shape[2]
        mask = (jnp.arange(max_len) <= length)[None, None, None, :]
    x = x_tok
    new_k, new_v = cache["k"], cache["v"]
    new_ks = cache.get("k_scale")
    new_vs = cache.get("v_scale")
    for i, blk in enumerate(params["blocks"]):
        q, k, v = _block_qkv(blk, x, heads)
        if quantized:
            kq, ks = _quantize_kv(k)        # (B,1,H,D), (B,1,H)
            vq, vs = _quantize_kv(v)
            # head-major column write at position `length`
            new_k = lax.dynamic_update_slice(
                new_k, jnp.transpose(kq, (0, 2, 3, 1))[None],
                (i, 0, 0, 0, length))
            new_v = lax.dynamic_update_slice(
                new_v, jnp.transpose(vq, (0, 2, 3, 1))[None],
                (i, 0, 0, 0, length))
            new_ks = lax.dynamic_update_slice(
                new_ks, jnp.transpose(ks, (0, 2, 1))[None],
                (i, 0, 0, length))
            new_vs = lax.dynamic_update_slice(
                new_vs, jnp.transpose(vs, (0, 2, 1))[None],
                (i, 0, 0, length))
            att = int8_cache_attend(q * inv_sqrt, new_k[i], new_ks[i],
                                    new_v[i], new_vs[i], mask_addend)
        else:
            new_k = lax.dynamic_update_slice(
                new_k, k[None].astype(new_k.dtype), (i, 0, length, 0, 0))
            new_v = lax.dynamic_update_slice(
                new_v, v[None].astype(new_v.dtype), (i, 0, length, 0, 0))
            att = _cache_attend(q, _positions_last(new_k[i]),
                                _positions_last(new_v[i]), mask)
        att = att.astype(x.dtype)
        x = x + matmul_any(att.reshape(batch, 1, embed),
                           blk["wout"]) + blk["bout"]
        x = _mlp(blk, x)
    logits = _head(params, x[:, 0])
    new = {"k": new_k, "v": new_v, "length": length + 1}
    if quantized:
        new["k_scale"] = new_ks
        new["v_scale"] = new_vs
    return logits, new


#: the decode-path weight matrices the int8 tier quantizes (everything
#: the per-token loop reads in bulk; norms and biases stay fp)
_QUANT_BLOCK_MATS = ("wqkv", "wout", "w1", "w2")


def quantize_params(params):
    """Weight-only int8 quantization of the decode-path matmuls
    (``ops/quant.py`` W8A16 recipe): every block projection and the
    vocab head become ``{"q8": int8, "scale": f32}`` leaves that
    ``matmul_any`` dequantizes inside the product. Norms, biases and
    the caller's embed table stay in the serving float dtype."""
    blocks.require_gpt2(params, "the int8 tiers' quantize_params",
                        tier="int8")
    qblocks = []
    for blk in params["blocks"]:
        qblk = dict(blk)
        for name in _QUANT_BLOCK_MATS:
            q, s = quantize_int8(blk[name])
            qblk[name] = {"q8": q, "scale": s}
        qblocks.append(qblk)
    q, s = quantize_int8(params["head"])
    return dict(params, blocks=qblocks, head={"q8": q, "scale": s})


def _pick_token(logits, key, temperature, sample, top_k):
    """Greedy (``sample=False``) or temperature sampling, optionally
    truncated to the top-k logits. Pure — runs inside the scan.
    ``sample``/``top_k`` are trace-time constants; ``temperature`` is a
    traced operand (a new value must NOT recompile the decode loop)."""
    if not sample:
        return jnp.argmax(logits, axis=-1)
    scaled = logits.astype(jnp.float32) / temperature
    if top_k:
        # lax.top_k, not a full vocab sort — this runs per token inside
        # the hot decode scan
        kth = lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled >= kth, scaled, -jnp.inf)
    return jax.random.categorical(key, scaled, axis=-1)


@functools.partial(jax.jit,
                   static_argnames=("heads", "n_tokens", "sample",
                                    "top_k"),
                   donate_argnames=("cache",))
def _generate_jit(params, embed_table, prompt_x, heads, n_tokens, cache,
                  key, temperature, sample, top_k):
    logits, cache = prefill(params, prompt_x, heads, cache)

    def body(carry, step_key):
        cache, logits = carry
        tok = _pick_token(logits, step_key, temperature, sample,
                          top_k)                                 # (B,)
        x_tok = embed_table[tok][:, None, :]                     # (B,1,E)
        logits, cache = decode_step(params, x_tok, heads, cache)
        return (cache, logits), tok

    # per-step keys by fold_in(key, step) — the SAME derivation the
    # continuous-batching slot engine uses per (request key, step), so
    # a slot's sampled stream reproduces generate(batch=1) exactly
    step_keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(n_tokens))
    (cache, logits), toks = lax.scan(body, (cache, logits), step_keys)
    return jnp.swapaxes(toks, 0, 1), logits, cache


def generate(params, embed_table, prompt_tokens, heads, n_tokens,
             max_len=None, temperature=0.0, top_k=0, key=None,
             quantize=None):
    """Decode ``n_tokens`` after ``prompt_tokens`` (B, T) int32 —
    greedy by default; ``temperature > 0`` samples (optionally truncated
    to the ``top_k`` highest logits) from the reproducible ``key``
    (defaults to the framework's named "decode" PRNG stream).

    ``quantize="int8"`` runs the W8A16 serving tier: the weight
    matrices are absmax-quantized once up front and the per-token loop
    reads them as int8 through the dequant-fused Pallas matvec
    (``ops/quant.py``) — half the bf16 tier's HBM traffic on the
    memory-bound loop. ``quantize="int8-kv"`` additionally stores the
    KV cache as int8 with per-(position, head) scales — at decode
    lengths the cache read rivals the weight read, so this halves the
    other half too. Pass an already-``quantize_params``-ed pytree to
    skip the requantization cost across calls.

    ``embed_table`` (vocab, E) maps tokens to the model's input
    embeddings (the toy model trains on pre-embedded x, so the table is
    the caller's). The prompt prefills the cache in one pass; the whole
    decode loop is one scan inside one jit with the cache donated.
    Returns ``(tokens (B, n_tokens), cache)``."""
    if quantize not in (None, "none", "int8", "int8-kv"):
        raise ValueError("quantize must be None, 'int8' or 'int8-kv', "
                         "got %r" % (quantize,))
    blocks.require_gpt2(params, "generate()'s scan over one shared cache")
    if quantize in ("int8", "int8-kv") \
            and not isinstance(params["head"], dict):
        params = quantize_params(params)
    batch, t = prompt_tokens.shape
    n_blocks = len(params["blocks"])
    embed = embed_table.shape[1]
    head_dim = embed // heads
    if max_len is None:
        max_len = t + n_tokens
    if max_len < t + n_tokens:
        raise ValueError("max_len %d < prompt %d + n_tokens %d"
                         % (max_len, t, n_tokens))
    if top_k < 0:
        raise ValueError("top_k must be >= 0, got %d" % top_k)
    top_k = min(int(top_k), embed_table.shape[0])  # clamp to the vocab
    if key is None:
        if temperature:
            from veles_tpu.core.prng import get as get_rng
            key = get_rng("decode").next_key()
        else:
            key = jax.random.key(0)  # unused by greedy, jit wants one
    if quantize == "int8-kv":
        # round the quantized cache up to whole 128-lane tiles: T is
        # the lane dimension of the head-major layout (masking makes
        # the extra positions inert)
        max_len = -(-max_len // 128) * 128
    # the cache follows the serving dtype: with bf16 params/table the
    # K/V traffic (comparable to the weight traffic at long context)
    # halves too — measured +~50% tokens/sec on the memory-bound loop
    cache = init_kv_cache(n_blocks, batch, max_len, heads, head_dim,
                          dtype=embed_table.dtype,
                          quantized=quantize == "int8-kv")
    prompt_x = embed_table[prompt_tokens]
    toks, _, cache = _generate_jit(params, embed_table, prompt_x, heads,
                                   n_tokens, cache, key,
                                   jnp.float32(temperature or 1.0),
                                   bool(temperature), int(top_k))
    return toks, cache


# -- continuous batching (slot engine) ----------------------------------------
#
# The serving tier's per-request loop: a fixed pool of cache SLOTS, each
# holding one in-flight sequence at its own length. New requests prefill
# into a free slot while other slots keep decoding — the "continuous
# batching" serving recipe (beyond-reference; VELES's serving analogue
# batches per tick, ``restful_api.py:78-215``). The math per slot is
# decode_step's exactly (same _block_qkv/_cache_attend/_head), with the
# scalar cache length generalized to a per-slot vector, the appends
# generalized to per-slot dynamic_update_slice at each slot's own
# length, and the attended span tiled to the longest live sequence
# (docs/serving_performance.md).


#: default attended-span tile (positions). The slot engine's per-step
#: attention and append traffic scale with
#: ``ceil((longest live sequence + chunk) / TILE) * TILE`` instead of
#: ``max_len`` — one compiled program per tile count, the same
#: compile-bounding trick as the prompt buckets. 128 = the TPU lane
#: width — T is the lane dimension of the int8-KV head-major layout.
SLOT_SPAN_TILE = 128
#: the most rungs the span ladder of a slab takes: past this many tiles
#: of ``SLOT_SPAN_TILE`` positions a rung is as many tiles as keep the
#: ladder to it (one compiled chunk program a rung)
SPAN_RUNGS = 16


def span_tile(max_len):
    """The span tile of a slab of ``max_len`` positions a slot:
    ``SLOT_SPAN_TILE`` up to ``SPAN_RUNGS`` tiles (2,048 positions),
    and whole tiles that make at most ``SPAN_RUNGS`` rungs past it."""
    tiles = -(-max_len // (SPAN_RUNGS * SLOT_SPAN_TILE))
    return max(1, tiles) * SLOT_SPAN_TILE


#: the state's control leaves. Beside them a slot holds two kinds of
#: state, as its blocks' kinds declare (``blocks.KINDS[...].leaves``).
#: ROWS A POSITION ``(S, row, T)``: under each name a tuple of one
#: array for every block that declares it, in the blocks' order
#: (``blocks.leaf_ordinals``): GPT-2's and grouped-query attention's
#: ``k`` and ``v``, with ``k_scale``/``v_scale`` in the int8-KV tier;
#: latent attention's one ``kv``. And, only where some block declares
#: it, FIXED STATE ``(S, ...)`` with no position axis, under ``FIXED``
#: as ``{name: tuple}`` alike: the short convolution's ``conv``
CONTROL_LEAVES = ("lengths", "logits", "req_key", "step")
FIXED = "fixed"


def _kv_names(state):
    """The names of the leaves that hold a row a position."""
    return sorted(name for name in state
                  if name not in CONTROL_LEAVES and name != FIXED)


def _ring_names(params):
    """The names of the leaves that are rings (``blocks.Kind.ring``):
    position ``p`` at ``p mod`` the leaf's length."""
    kinds = blocks.block_kinds(blocks.arch_of(params),
                               len(params["blocks"]))
    return sorted({name for kind in kinds if kind.ring
                   for name in kind.names})


def ring_window(before, length, j):
    """``(start, low)``, (S,) each: what step ``j`` of a chunk sees of
    a ring of ``length`` positions, the chunk having begun where each
    slot's sequence stood, ``before`` (S,). Entry ``r`` holds position
    ``before - length + age``, its age ``(r - start) mod length``: seen
    where that position is one (``>= 0``) and lies inside the window of
    the step's own, ``before + j``, which takes the last ``length``
    positions with its own: where ``age >= low``. The entries this
    chunk's staged columns will replace are the ones the window has
    left by then."""
    return before % length, jnp.maximum(j + 1, length - before)


def ring_visible(before, length, read, j):
    """``(S, read)`` bool: which of the first ``read`` entries of the
    ring step ``j`` sees (:func:`ring_window`)."""
    start, low = ring_window(before, length, j)
    age = (jnp.arange(read)[None, :] - start[:, None]) % length
    return age >= low[:, None]


def ring_of(columns, lengths, length):
    """What a ring of ``length`` positions holds of a prompt's
    ``columns`` (B, ..., T), ``lengths`` (B,) the rows' true lengths:
    entry ``r`` takes the last position ``p < length_b`` with ``p mod
    length == r`` (none where the prompt is shorter: an entry no step
    sees). ``(B, ..., length)``."""
    last = lengths[:, None] - 1
    at = last - (last - jnp.arange(length)[None, :]) % length
    at = jnp.maximum(at, 0).reshape(
        (at.shape[0],) + (1,) * (columns.ndim - 2) + (length,))
    return jnp.take_along_axis(columns, at, axis=-1)


def init_slot_state(n_blocks, slots, max_len, heads, head_dim, vocab,
                    dtype=jnp.float32, quantized=False, mesh=None,
                    mesh_axis="model", paged=False, pages=None,
                    page_size=None, formats=None, arch=blocks.GPT2):
    """Cache + control state for ``slots`` concurrent sequences.

    The cache's leaves are the ones the kinds of ``arch``'s blocks
    declare (``blocks.KINDS``; ``CONTROL_LEAVES`` above has the
    state's outline): rows a position as tuples of arrays ``(S,
    row..., T)``, positions minor (latent attention's one ``kv`` of
    ``(S, kv_rank + rope_dim, T)``; grouped-query attention's ``k``
    and ``v`` of ``(S, kv_heads * head_dim, T)``; GPT-2's as follows),
    and fixed state a slot ``(S, ...)`` under ``FIXED`` (the short
    convolution's ``(S, (taps - 1) * E)``).

    A kind whose rows lie in a ring (``"swa"``: ``k_ring`` and
    ``v_ring``) has leaves of its own length, ``kind.positions``: a
    window layer's ring beside the global layers' ``max_len``.

    The slab is ONE K and ONE V leaf per block, ``state["k"]`` and
    ``state["v"]`` tuples of ``n_blocks`` arrays ``(S, H·D, T)``:
    heads and ``head_dim`` folded, head-major, positions minor. Both
    minor dimensions are whole tiles (``H·D`` and a ``max_len`` that
    are multiples of 128), so no device layout pads a leaf: a
    ``(…, H, 64)`` minor dimension costs twice its bytes on the chip,
    in HBM and in every read of the attend. A leaf per block is what
    lets the chunk program update each in place: one stacked ``(L, …)``
    array is copied whole round the scan
    (docs/serving_performance.md).

    ``quantized=True`` stores the leaves as int8 ``(S, H, D, T)`` with
    per-(slot, head, position) f32 scales ``(S, H, T)`` in
    ``k_scale``/``v_scale`` — ``init_kv_cache``'s int8-KV recipe
    generalized to the slot pool (``int8_cache_attend``'s order), so
    continuous serving gets the same halved cache traffic as raw
    ``generate(quantize="int8-kv")``.

    ``formats`` (:func:`decide_slot_formats`) builds the K/V leaves in
    the device layout the decode programs were found to work in; the
    slot programs then pin what the leaves carry (:func:`slot_fns`).
    Without it the leaves have the platform's default layout.

    ``mesh`` creates the state already in the serving layout: the KV
    leaves (and the int8 tier's scales) sharded over their heads dim on
    ``mesh_axis``, control leaves replicated — per-device slot-cache
    HBM then scales with H/n (:func:`slot_state_specs`).

    ``paged=True`` swaps the dense per-slot slab for the page-pool
    layout (``parallel/kv_pool.py``): one ``pages`` x ``page_size``
    pool (default: the slab-equivalent ``slots x ceil((max_len + 2) /
    page_size)`` plus the scratch page; the serving decoder sizes its
    own default with ``chunk=n_tokens`` dispatch slack) shared by
    every slot through a
    host page table, created in-layout under ``mesh`` exactly like the
    slab (pool pages shard over HEADS)."""
    if paged:
        if arch != blocks.GPT2:
            raise ValueError(
                "the page pool (parallel/kv_pool.py) holds k/v pages "
                "of heads x head_dim; %s has no paged cache "
                "yet" % blocks.kinds_said(arch))
        from veles_tpu.parallel.kv_pool import (default_pool_pages,
                                                init_paged_state)

        if page_size is None:
            page_size = SLOT_SPAN_TILE
        if pages is None:
            pages = default_pool_pages(slots, max_len, page_size)
        return init_paged_state(
            n_blocks, pages, page_size, heads, head_dim, vocab, slots,
            dtype=dtype, quantized=quantized, mesh=mesh,
            mesh_axis=mesh_axis)
    state = {
        "lengths": jnp.zeros((slots,), jnp.int32),
        "logits": jnp.zeros((slots, vocab), jnp.float32),
        # per-slot sampling stream: the request's key + how many tokens
        # it has generated (step key = fold_in(req_key, step) — the
        # derivation generate() shares, so sampled streams match)
        "req_key": jax.random.split(jax.random.key(0), slots),
        "step": jnp.zeros((slots,), jnp.int32),
    }
    # name -> one shape for every block that declares the leaf
    leaves, fixed = {}, {}
    for kind in blocks.block_kinds(arch, n_blocks):
        for name, (row, leaf_dtype) in kind.leaves(
                arch, heads, head_dim, dtype, quantized).items():
            if kind.fixed:
                fixed.setdefault(name, []).append(
                    jax.ShapeDtypeStruct((slots,) + row, leaf_dtype))
            else:
                # a ring kind's leaves are its own length (its window)
                leaves.setdefault(name, []).append(jax.ShapeDtypeStruct(
                    (slots,) + row + (kind.positions(arch, max_len),),
                    leaf_dtype))
    # where each leaf lives: every leaf is committed to its place, so
    # the first dispatch and every later one (whose state is a
    # program's output) are the same call to the same program
    if mesh is not None:
        from jax.sharding import NamedSharding

        place = {name: NamedSharding(mesh, spec[0] if name in leaves
                                     else spec)
                 for name, spec in slot_state_specs(
                     n_blocks, quantized, axis=mesh_axis).items()}
    else:
        from jax.sharding import SingleDeviceSharding

        here = SingleDeviceSharding(jax.devices()[0])
        place = dict.fromkeys(list(state) + list(leaves), here)
    if formats is not None:
        place.update(formats)
    state = {name: jax.device_put(leaf, place[name])
             for name, leaf in state.items()}
    shapes = {name: tuple(each) for name, each in leaves.items()}
    where = {name: place[name] for name in leaves}
    if fixed:
        # the fixed state lies where the control leaves do
        shapes[FIXED] = {name: tuple(each) for name, each in fixed.items()}
        where[FIXED] = place["lengths"]
    # the slab is made where and how it will lie (a program's outputs
    # in their pinned place): no second copy of it exists meanwhile
    state.update(jax.jit(
        lambda: jax.tree.map(lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
                             shapes),
        out_shardings=where)())
    return state


def slot_state_bytes(state):
    """Device bytes of a slot/paged decode state pytree — the
    ``decode_state`` memscope accountant's sizing primitive. For the
    paged layout the PAGE leaves are charged to the ``kv_pool`` owner
    instead (``kv_pool.paged_kv_bytes``), so callers subtract."""
    from veles_tpu.observe.memscope import pytree_nbytes
    return pytree_nbytes(state)


def param_tree_bytes(params, embed_table=None):
    """Device bytes of a parameter tree (plus the tied embedding table
    when it is a separate leaf) — the ``params`` / ``param_stash``
    memscope accountants' sizing primitive."""
    from veles_tpu.observe.memscope import pytree_nbytes
    return pytree_nbytes(params) + pytree_nbytes(embed_table)


def _slot_admit_many(params, embed_table, heads, state, slots,
                     prompt_x, req_keys, lengths):
    """Admit a whole same-bucket group in ONE dispatch: prefill
    ``prompt_x`` (B, T, E) — each row right-padded to the bucket T —
    and scatter what each block keeps of them into slots ``slots``
    (B,) int32 of the block's leaves: the K/V rows of positions
    [0, T), or the fixed state after each row's TRUE length, set
    whole (nothing of a retired occupant's state stays). A ring
    shorter than the bucket takes each row's last positions, each at
    its place in the ring (:func:`ring_of`).

    The prefill cost scales with the BUCKET (T), not ``max_len``: only
    positions [0, T) of each slot lane are written. Stale positions
    beyond the bucket from a retired occupant are harmless — a lane's
    position is always (re)written by this sequence's own append
    before its mask first exposes it. One compiled program per
    (bucket, group size); the host pads a group to a power-of-two size
    with DUPLICATE rows (identical slot/prompt/key/length), which is
    well-defined because duplicate scatter writes carry equal values.

    ``req_keys`` (B,) seeds each slot's sampling stream; ``lengths``
    (B,) are the true prompt lengths inside the padded rows."""
    t = prompt_x.shape[1]
    kinds = blocks.block_kinds(blocks.arch_of(params),
                               len(params["blocks"]))
    # named after the host-side "decode.admit" span so the XLA device
    # trace and the span timeline line up in a profiler capture
    # (observe/profile.py; zero cost post-compile)
    with jax.named_scope("decode.admit"):
        logits, rows_all, lengths = _prompt_forward(
            params, prompt_x, heads, lengths, embed_table)
        # the sampling stream's books, then positions [0, t) of each
        # admitted slot's K/V lane
        with jax.named_scope("sample"):
            new = dict(
                state,
                lengths=state["lengths"].at[slots].set(lengths),
                logits=state["logits"].at[slots].set(
                    logits.astype(jnp.float32)),
                req_key=state["req_key"].at[slots].set(req_keys),
                step=state["step"].at[slots].set(
                    jnp.zeros_like(lengths)),
            )
        with jax.named_scope("cache.append"):
            # positions [0, t) of each admitted slot's lane, block by
            # block: a block's rows (B, ..., T) are turned and written
            # on their own, so no second copy of all blocks' rows
            # stands beside what the prefill returns
            fresh = {name: list(state[name]) for name in _kv_names(state)}
            fixed = {name: list(leaves)
                     for name, leaves in state.get(FIXED, {}).items()}
            for kind, i, rows in zip(kinds, blocks.leaf_ordinals(kinds),
                                     rows_all):
                if kind.fixed:      # a slot's state, set whole
                    for name, value in sorted(kind.columns(
                            state[FIXED], rows).items()):
                        fixed[name][i] = kind.put(fixed[name][i], slots,
                                                  value)
                    continue
                # positions [0, t) of a slot's lane
                for name, value in sorted(kind.columns(state,
                                                       rows).items()):
                    length = fresh[name][i].shape[-1]
                    where = (slots, Ellipsis, slice(None, min(t, length)))
                    if t <= length:
                        fresh[name][i] = fresh[name][i].at[where].set(
                            value)
                        continue
                    with jax.named_scope("cache.ring"):
                        fresh[name][i] = fresh[name][i].at[where].set(
                            ring_of(value, lengths, length))
            new.update({name: tuple(leaves)
                        for name, leaves in fresh.items()})
            if fixed:
                new[FIXED] = {name: tuple(leaves)
                              for name, leaves in fixed.items()}
    return new


def slot_admit(params, embed_table, heads, state, slot, prompt_x,
               req_key=None, length=None):
    """Prefill ``prompt_x`` (1, T, E) into slot ``slot`` — the B=1
    case of :func:`slot_admit_many` (one compiled program per prompt
    bucket T; the prefill cost scales with the bucket, not
    ``max_len``). ``req_key`` seeds the slot's sampling stream
    (ignored by greedy serving); ``length`` marks the true prompt
    length of a right-padded ``prompt_x``."""
    if req_key is None:
        req_key = jax.random.key(0)
    if length is None:
        length = prompt_x.shape[1]
    return slot_admit_many(
        params, embed_table, heads, state,
        jnp.reshape(jnp.asarray(slot, jnp.int32), (1,)), prompt_x,
        jnp.stack([req_key]),
        jnp.reshape(jnp.asarray(length, jnp.int32), (1,)))


def _slot_steps(params, embed_table, heads, state, active, n,
                temperature, sample, top_k, span, place=None):
    """``n`` lockstep decode steps across ALL slots, the cache's
    leaves used in place: ``(state, emitted (n, S))``. A model with
    routed experts emits ``(tokens (n, S), load (n, blocks, experts))``:
    beside the tokens, the assignments per expert of each expert block
    at each step, over the active slots (:func:`split_emitted`).

    Positions are the leaves' minor dimension, so one slot's new
    column is a strided write that costs an op of its own (3.5 us
    each on a v5e, 2.7 ms a step for 16 slots x 48 leaves: PERF.md),
    and each slot appends at its own length. The chunk therefore
    STAGES its columns: step ``j`` writes every slot's column at once
    into column ``j`` of a small ``(..., n)`` buffer per leaf (one
    uniform write a leaf), attends over the leaf's window, which holds
    what was cached before the chunk, and the staged columns up to its
    own, and only when the steps are done does each slot's block of
    ``n`` columns go to the leaf at the length the slot had when the
    chunk began: as :func:`block_write_path` says, one Pallas call
    over the leaves (``ops/slab_write.write_blocks``), or one write per
    slot and leaf from a loop over the slots. A ring's block goes to
    that length modulo the ring's, split where it wraps, and a step
    sees of a ring what :func:`ring_visible` says.

    ``place`` is where the state lies, leaf name -> ``Format``, as
    :func:`slot_fns` pins it on the program it builds around this
    function; None where nobody knows (an outer trace holds the
    state). A step attends as ``blocks.attend_path`` says of it: each
    slot over its own length by the attention kind's kernel, or every
    slot over the window of ``span`` positions."""
    slots = state["lengths"].shape[0]
    quantized = "k_scale" in state
    names = _kv_names(state)
    arch = blocks.arch_of(params)
    kinds = blocks.block_kinds(arch, len(params["blocks"]))
    ordinals = blocks.leaf_ordinals(kinds)
    head_dim = embed_table.shape[1] // heads
    # a model whose blocks all carry a fixed state has no row a
    # position: no window to attend, no span, no column to stage
    rings = _ring_names(params)
    if names:
        # positions are minor; a ring is no longer than the others
        max_len = max(state[name][0].shape[-1] for name in names)
        if span is None or span > max_len:
            span = max_len
    else:
        span = 0
    before = state["lengths"]
    where = place[names[0]].sharding if place and names else None
    ragged = blocks.attend_path(params, state, where) == "kernel"
    # where the fixed state lies, for the kinds whose step has a
    # kernel to choose (blocks.state_path asks the same of the same)
    fixed_place = place and place.get(FIXED)
    # what a slot has cached is what it held when the chunk began: said
    # as lengths where each slot attends over its own (an idle lane's
    # answer is no one's, so it reads nothing), else as a mask over the
    # window (position p of slot s is cached iff p < the slot's
    # length); a staged column is visible from its own step on (the new
    # token attends to itself)
    with jax.named_scope("attn.attend"):
        cached = jnp.where(active, before, 0) if ragged \
            else jnp.arange(span)[None, :] < before[:, None]

    def masks(visible):
        if quantized:
            return jnp.where(visible, 0.0, -1e30).astype(jnp.float32)
        return visible[:, None, None, :]

    def step(carry, j):
        control, staged, fixed = carry
        lengths = control["lengths"]
        # the named scopes of a step (HLO metadata; the scope table,
        # observe/xla_stats.scope_table, carries them to a traced op):
        # sample, embed, then per block attn.qkv (the kind's project),
        # cache.append, cache.read, attn.attend, attn.out, mlp (ffn),
        # then head
        with jax.named_scope("sample"):
            if sample:
                step_keys = jax.vmap(jax.random.fold_in)(
                    control["req_key"], control["step"])
                # inner shape (1, V): the SAME categorical shape
                # generate's batch-1 path draws, so the random bits
                # match exactly
                tok_in = jax.vmap(
                    lambda l, k: _pick_token(l[None], k, temperature,
                                             True, top_k)[0])(
                    control["logits"], step_keys)
            else:
                tok_in = jnp.argmax(control["logits"], axis=-1)
        with jax.named_scope("embed"):
            x = embed_table[tok_in][:, None, :]
        with jax.named_scope("attn.attend"):
            mask = None if ragged else masks(cached)
            mask_staged = masks(jnp.broadcast_to(
                jnp.arange(n)[None, :] <= j, (slots, n)))
            if rings:
                # a ring's read is its first min(span, length) entries:
                # all of it once a sequence may have wrapped
                length = state[rings[0]][0].shape[-1]
                if ragged:
                    ring_at = ring_window(before, length, j)
                else:
                    mask_ring = masks(ring_visible(before, length,
                                                   min(span, length), j))
        staged = {name: list(staged[name]) for name in names}
        fixed = {name: list(fixed[name]) for name in fixed}

        def attend(blk, kind, i, q, rows):
            # every slot's new column at once, into column j of this
            # block's staging buffers: (S, H·D, 1); the int8 tier's
            # (S, H, D, 1) and (S, H, 1); latent attention's (S, W, 1)
            with jax.named_scope("cache.append"):
                mine = kind.columns(state, rows)
                for name, cols in mine.items():
                    at = (0,) * (cols.ndim - 1) + (j,)
                    staged[name][i] = lax.dynamic_update_slice(
                        staged[name][i], cols, at)
            leaves = {name: state[name][i] for name in mine}
            columns = {name: staged[name][i] for name in mine}
            if ragged and kind.ring:
                # a ring's entries are live up to its length
                length = state[rings[0]][0].shape[-1]
                return kind.attend_ragged(
                    q, leaves, columns, jnp.minimum(cached, length),
                    min(span, length), mask_staged, ring=ring_at)
            if ragged:
                return kind.attend_ragged(q, leaves, columns, cached, span,
                                          mask_staged)
            # ONE read per leaf: the attended window, consumed by the
            # attend from the leaf where it lies; never the leaf at
            # max_len
            with jax.named_scope("cache.read"):
                read = {name: leaf[..., :min(span, leaf.shape[-1])]
                        for name, leaf in leaves.items()}
            return kind.attend_cached(arch, blk, q, read, columns,
                                      mask_ring if kind.ring else mask,
                                      mask_staged)

        loads = []
        for blk, kind, i in zip(params["blocks"], kinds, ordinals):
            # the new token stands at its slot's own length
            q, rows = kind.project(arch, blk, x, heads, lengths[:, None])
            if kind.fixed:
                # no row a position: the block reads the state its
                # slot carries (under the names of what it projected)
                # and rewrites it (an idle lane's stays)
                att, new = kind.step(
                    arch, blk, q, rows,
                    {name: fixed[name][i] for name in kind.leaves(
                        arch, heads, head_dim, x.dtype)},
                    active, fixed_place)
                for name, value in new.items():
                    fixed[name][i] = value
            else:
                att = attend(blk, kind, i, q, rows)
            if arch.parallel:
                x, load = blocks.block_rest(arch, blk, kind, x, att,
                                            active[:, None])
            else:
                x = kind.out(blk, x, att)
                # an idle slot's lane is computed, but routed to no
                # expert
                x, load = blocks.ffn(arch, blk, x, active[:, None])
            if load is not None:
                loads.append(load)
        logits = blocks.head(arch, params, x[:, 0],
                             embed_table).astype(jnp.float32)
        with jax.named_scope("sample"):
            control = dict(
                control,
                lengths=jnp.where(active, lengths + 1, lengths),
                logits=jnp.where(active[:, None], logits,
                                 control["logits"]),
                step=jnp.where(active, control["step"] + 1,
                               control["step"]))
        return (control, {name: tuple(staged[name]) for name in names},
                {name: tuple(fixed[name]) for name in fixed}), \
            ((tok_in, jnp.stack(loads)) if loads else tok_in)

    control = {name: state[name] for name in CONTROL_LEAVES}
    staged = {name: tuple(jnp.zeros(leaf.shape[:-1] + (n,), leaf.dtype)
                          for leaf in state[name]) for name in names}
    # the fixed state rides in the carry and is the chunk's result as
    # the last step left it: written back once a chunk
    (control, staged, fixed), emitted = lax.scan(
        step, (control, staged, state.get(FIXED, {})), jnp.arange(n))
    # each slot's block of n columns, to where the slot's sequence
    # stood. An inactive lane's block is what its frozen logits made:
    # it lands past the lane's length, where nothing reads before the
    # lane's own appends or a new occupant's prefill have rewritten it
    # (a block that would pass max_len is clamped back onto the lane's
    # end, as the single append was: only a sequence that has overrun
    # its budget, whose tokens the host discards, stands there).
    new_state = dict(control)
    if fixed:
        new_state[FIXED] = fixed
    with jax.named_scope("cache.append"):
        write = slab_write.write_blocks \
            if block_write_path(state, where, n) == "kernel" \
            else slab_write.write_blocks_loop
        written = iter(write(
            [leaf for name in names for leaf in state[name]],
            [block for name in names for block in staged[name]], before,
            rings=[name in rings for name in names
                   for _ in state[name]]))
        for name in names:
            new_state[name] = tuple(next(written) for _ in state[name])
    return new_state, emitted


def block_write_path(state, sharding, n):
    """How a chunk of ``n`` steps over the slot state ``state``
    (arrays, tracers or shapes), whose K/V leaves lie as ``sharding``
    says (None: nobody knows), writes its staged blocks to the leaves:
    ``"kernel"`` where the rule (``ops/slab_write.use_write_kernel``)
    takes every leaf that holds a row a position, else ``"loop"``; the
    int8-KV tier (leaves and scales alike) keeps the loop. None for a
    state with no such leaf. The ONE question: :func:`_slot_steps`
    asks it when a program is traced for a place, the decoder asks it
    of the state it holds for its books."""
    names = _kv_names(state)
    if not names:
        return None
    if "k_scale" not in state and all(
            slab_write.use_write_kernel(state[name][0], sharding, n)
            for name in names):
        return "kernel"
    return "loop"


def split_emitted(emitted):
    """``(tokens, load)`` of what a chunk emits; ``load`` is None for
    a model without routed experts."""
    return emitted if isinstance(emitted, tuple) else (emitted, None)


def _slot_step(params, embed_table, heads, state, active,
               temperature=1.0, sample=False, top_k=0, span=None,
               place=None):
    """One decode step across ALL slots; ``active`` (S,) bool gates
    which slots advance (inactive slots' lanes are computed but their
    lengths/logits stay frozen and their emitted token is meaningless —
    the host filters by its own active set). Greedy by default;
    ``sample=True`` draws per slot from its own key stream
    (``fold_in(req_key, step)``) so a slot's sampled tokens equal
    ``generate(batch=1, key=req_key)``'s. Returns ``(state, emitted
    (S,))`` where ``emitted[s]`` is the token slot ``s`` generates THIS
    step — picked from the pre-step logits, matching ``generate``'s
    emission order (its first emitted token comes from the prefill
    logits).

    ``span`` (static, default ``max_len``) tiles the attended cache
    prefix: attention reads positions [0, span) only, so the per-step
    cost scales with the longest LIVE sequence (rounded up to
    ``SLOT_SPAN_TILE`` by the host) instead of ``max_len``. The host
    must pass ``span > max(lengths[active])`` — masked positions
    beyond a sequence's length contribute exact zeros, so any
    sufficient span produces identical tokens. Appends still write
    into the full-length leaves (one K and one V per block, positions
    minor: :func:`init_slot_state`). An inactive lane whose length reaches
    ``max_len`` keeps (harmlessly) rewriting the last position — its
    output is discarded and a re-admitted slot rewrites every position
    before attending to it. The one-step case of :func:`_slot_steps`."""
    state, emitted = _slot_steps(params, embed_table, heads, state,
                                 active, 1, temperature, sample, top_k,
                                 span, place)
    return state, split_emitted(emitted)[0][0]


def _slot_step_many(params, embed_table, heads, state, active, n,
                    temperature=1.0, sample=False, top_k=0, span=None,
                    place=None):
    """``n`` lockstep ``slot_step``s as ONE dispatch (``lax.scan``
    inside :func:`_slot_steps`) — the throughput mode: admission
    happens between chunks, so a high-RTT host pays one round trip per
    ``n`` tokens instead of per token. ``span`` (static) must cover
    the longest live sequence plus the whole chunk (each step appends
    one position). Returns ``(state, emitted (n, S))``; the host
    discards a slot's tail tokens past its budget/eos."""
    # named after the host-side "decode.dispatch" span (the profiler
    # alignment contract — observe/profile.py): the whole chunk shows
    # up as one labeled region in the XLA device trace
    with jax.named_scope("decode.dispatch"):
        return _slot_steps(params, embed_table, heads, state, active, n,
                           temperature, sample, top_k, span, place)


# -- the jitted surface --------------------------------------------------------
#
# The slot programs exist once per PLACE of the K/V leaves: where they
# are sharded and in which device layout they lie. Every program that
# takes or returns the state is compiled with that place pinned on the
# K/V leaves, in and out, and the control leaves' shardings with them:
# the donated buffers then alias, no program converts the slab at its
# boundary, and a donated state can never drift off the canonical
# layout and defeat the jit cache (one compiled program per (bucket,
# group) or span, per place). The place is not configured: it is read
# off the state's own leaves (:func:`slot_fns`), which
# :func:`init_slot_state` built where :func:`decide_slot_formats` or
# the platform's default put them.

_SLOT_FNS = {}              # place of the K/V leaves -> the three jits
_DECIDED_FORMATS = {}       # state skeleton and place -> {name: Format}
_SLOT_FNS_LOCK = threading.Lock()


def _pinned_place(kv_place, control, fixed=False):
    """The state's prefix tree of places: each K/V name's, and
    ``control`` on every control leaf and on the ``fixed`` state
    (where the state has any)."""
    names = CONTROL_LEAVES + ((FIXED,) if fixed else ())
    return dict(dict.fromkeys(names, control), **kv_place)


def slot_fns(state):
    """``(admit_many, step, step_many)`` for ``state``: the raw
    functions (one copy of the math — the bit-identity contract) jitted
    with the state donated and its place pinned in and out, the emitted
    tokens replicated. Instrumented under the program names of the
    host spans, so the veles_xla_* counters, profiler spans and
    flight-recorder vocabulary are the same wherever the state lies.

    The place is read off the state's arrays: each K/V name's layout
    and sharding (one per name: its leaves are built alike), and for
    the control leaves and the fixed state (in the platform's default
    layout) the sharding that stands for "replicated" where
    the K/V are (on one device, that device: a place that is pinned
    lowers the same program whether or not the arrays handed in are
    committed to it, which is what lets ``xla_stats.scope_table`` find
    the program that ran from shapes alone). A state that an outer
    trace holds has no place to read: nothing is pinned for it.

    The check-then-insert is LOCKED: two tiers of the same place built
    concurrently (a breaker rebuild racing a new API) must share one
    jit object, not compile twice."""
    names = _kv_names(state)
    # (a model with no row a position: the control leaves say where)
    lead = state[names[0]][0] if names else state["lengths"]
    concrete = isinstance(lead, jax.Array) \
        and not isinstance(lead, jax.core.Tracer)
    key = (tuple((name, state[name][0].format) for name in names),
           FIXED in state, lead.sharding) if concrete else None
    with _SLOT_FNS_LOCK:
        fns = _SLOT_FNS.get(key)
    if fns is not None:
        return fns
    place = None
    if concrete:
        control = lead.sharding
        if getattr(control, "mesh", None) is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            control = NamedSharding(control.mesh, P())
        place = _pinned_place(dict(key[0]), control, key[1])
    fns = _build_slot_fns(place)
    with _SLOT_FNS_LOCK:
        # a racing builder may have won; keep ITS jit objects (their
        # compiled programs are already cached)
        return _SLOT_FNS.setdefault(key, fns)


def _build_slot_fns(place):
    """The three jit objects with ``place`` pinned (``None``: nothing
    pinned, for a state that an outer trace holds) and told to the
    step programs, whose attend depends on it (``_slot_steps``): a
    tracer does not say where it lies, the builder of its program
    knows. Statics are positional: jit takes no keyword beside pinned
    operand places."""
    def placed(fn):
        # under fn's own name: it names the compiled module, which is
        # what a trace's readers look for
        return functools.wraps(fn)(functools.partial(fn, place=place))

    pins = ({}, {}, {})
    if place is not None:
        emitted = place["lengths"]
        pins = (
            dict(in_shardings=(None, None, place, None, None, None,
                               None),
                 out_shardings=place),
            dict(in_shardings=(None, None, place, None, None),
                 out_shardings=(place, emitted)),
            dict(in_shardings=(None, None, place, None, None),
                 out_shardings=(place, emitted)))
    # compile/cache-hit/FLOPs telemetry per slot program
    # (observe/xla_stats.py): each name is that of the program's host
    # span and outermost named_scope, so the veles_xla_* counters and
    # the span vocabulary agree. A profiler capture holds the span
    # names on its host plane and, on its device plane, each
    # instruction's text and no scope: the scopes reach a traced op
    # only through the scope table (xla_stats.scope_table), for which
    # the wrappers note every program they dispatch while the tracer is
    # on. With telemetry and tracing off a wrapper delegates after two
    # attribute checks.
    return (
        instrument("decode.admit", jax.jit(
            _slot_admit_many, static_argnums=(2,), donate_argnums=(3,),
            **pins[0])),
        instrument("decode.step", jax.jit(
            placed(_slot_step), static_argnums=(2, 6, 7, 8),
            donate_argnums=(3,), **pins[1])),
        instrument("decode.dispatch", jax.jit(
            placed(_slot_step_many), static_argnums=(2, 5, 7, 8, 9),
            donate_argnums=(3,), **pins[2])))


def slot_admit_many(params, embed_table, heads, state, slots, prompt_x,
                    req_keys, lengths):
    """:func:`_slot_admit_many` as one dispatch, the state donated."""
    return slot_fns(state)[0](params, embed_table, heads, state, slots,
                              prompt_x, req_keys, lengths)


def slot_step(params, embed_table, heads, state, active,
              temperature=1.0, sample=False, top_k=0, span=None):
    """:func:`_slot_step` as one dispatch, the state donated."""
    return slot_fns(state)[1](params, embed_table, heads, state, active,
                              temperature, sample, top_k, span)


def slot_step_many(params, embed_table, heads, state, active, n,
                   temperature=1.0, sample=False, top_k=0, span=None):
    """:func:`_slot_step_many` as one dispatch, the state donated."""
    return slot_fns(state)[2](params, embed_table, heads, state, active,
                              n, temperature, sample, top_k, span)


# the ledger's per-dispatch attribution key (dispatch_program below)
slot_admit_many.program_name = "decode.admit"
slot_step.program_name = "decode.step"
slot_step_many.program_name = "decode.dispatch"
_generate_jit = instrument("decode.generate", _generate_jit)


def decide_slot_formats(params, embed_table, heads, state, n, span,
                        mesh=None, mesh_axis="model"):
    """The device layout of the K/V leaves, taken from the compiler:
    ``{leaf name: Format}`` for :func:`init_slot_state`'s ``formats``.

    One representative chunk program (``n`` steps over ``span``
    positions, through the first two of the blocks that hold each
    leaf: every block uses its leaves alike, and two compile in a
    second where all of them take a quarter of a minute of every
    set-up; a block that keeps no row a position has no leaf to
    decide and stays out) is compiled with the layout
    of every K/V leaf left to the compiler, in and out
    (``Layout.AUTO``), and the layout it chose for the leaves it takes
    is the layout the loop works in: pinned on every program from then
    on, nothing is converted at a program's boundary. ``state`` is the state's skeleton
    (``jax.eval_shape`` of :func:`init_slot_state`); under ``mesh`` its
    leaves are sharded as :func:`slot_state_specs` says, else they lie
    where the skeleton's leaves say (``sharding``) or on the first
    device. One layout per name: where the compiler's choice differs
    between the two, the first block's stands. Decided once per leaf
    skeleton and place in a process (a breaker's rebuild asks again)."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    names = _kv_names(state)
    if not names:           # no row a position: no layout to decide
        return {}
    if mesh is not None:
        specs = slot_state_specs(len(state[names[0]]),
                                 "k_scale" in state, axis=mesh_axis)
        where = {name: NamedSharding(mesh, specs[name][0])
                 for name in names}
        control = NamedSharding(mesh, P())
    else:
        here = SingleDeviceSharding(jax.devices()[0])
        where = {name: getattr(state[name][0], "sharding", None) or here
                 for name in names}
        control = where[names[0]]
    memo = (tuple((name, state[name][0].shape, state[name][0].dtype,
                   where[name]) for name in names), heads, n, span)
    with _SLOT_FNS_LOCK:
        formats = _DECIDED_FORMATS.get(memo)
    if formats is not None:
        return formats
    place = _pinned_place(
        {name: Format(Layout.AUTO, where[name]) for name in names},
        control)
    slots = state["lengths"].shape[0]
    arch = blocks.arch_of(params)
    kinds = blocks.block_kinds(arch, len(params["blocks"]))
    kept = [i for i, (kind, at) in enumerate(
        zip(kinds, blocks.leaf_ordinals(kinds)))
        if at < 2 and not kind.fixed]
    params = dict(params, blocks=[params["blocks"][i] for i in kept])
    if not isinstance(arch.layers, str):
        params["arch"] = dataclasses.replace(
            arch, layers=tuple(arch.layers[i] for i in kept))
    state = {name: state[name][:2] if name in names else state[name]
             for name in state if name != FIXED}
    chosen = _build_slot_fns(place)[2].__wrapped__.lower(
        params, embed_table, heads, state,
        jax.ShapeDtypeStruct((slots,), jnp.bool_), n,
        jax.ShapeDtypeStruct((), jnp.float32), False, 0,
        span).compile().input_formats[0][2]
    formats = {name: chosen[name][0] for name in names}
    with _SLOT_FNS_LOCK:
        return _DECIDED_FORMATS.setdefault(memo, formats)


def slot_layout_facts(state):
    """What a run's record says of the layout it ran in: per K/V name
    the leaves' ``major_to_minor`` and tiling as the arrays report
    them, and the state's device bytes in that layout (a tiled layout
    pads; ``nbytes`` does not know)."""
    facts = {}
    for name in _kv_names(state):
        layout = state[name][0].format.layout
        facts[name] = {
            "major_to_minor": list(layout.major_to_minor),
            "tiling": [list(tile) for tile in layout.tiling or ()]}
    facts["state_device_bytes"] = sum(
        shard.data.on_device_size_in_bytes()
        for leaf in jax.tree.leaves(state)
        for shard in leaf.addressable_shards)
    return facts


def slot_holds(params, state):
    """What a slot of the dense ``state`` holds, for the books: the
    model's blocks by kind (``block_kinds``) and a slot's bytes as
    rows a position (all blocks that keep one, one position), as fixed
    state and, for a model with rings, as its rings whole."""
    import collections

    names = blocks.layer_names(blocks.arch_of(params),
                               len(params["blocks"]))
    slots = state["lengths"].shape[0]
    rings = _ring_names(params)
    rows = sum(leaf.nbytes // (slots * leaf.shape[-1])
               for name in _kv_names(state) if name not in rings
               for leaf in state[name])
    fixed = sum(leaf.nbytes // slots
                for leaf in jax.tree.leaves(state.get(FIXED, {})))
    holds = {"block_kinds": dict(collections.Counter(names)),
             "slot_row_bytes_per_position": int(rows),
             "slot_fixed_state_bytes": int(fixed)}
    if rings:
        holds["slot_ring_bytes"] = int(sum(
            leaf.nbytes // slots for name in rings for leaf in state[name]))
    return holds


def slot_attend_path(params, state):
    """``"kernel"`` or ``"xla"``: how the step programs that
    :func:`slot_fns` builds for ``state`` (arrays) attend the cache.
    ``blocks.attend_path`` asked what ``_slot_steps`` asks it, with
    the place the programs are told: the one the K/V leaves lie in."""
    names = _kv_names(state)
    return blocks.attend_path(
        params, state, state[names[0]][0].sharding if names else None)


def slot_write_path(state, n):
    """``"kernel"``, ``"loop"`` or None (no row a position): how the
    step programs that :func:`slot_fns` builds for ``state`` (arrays)
    write a chunk of ``n`` steps' staged blocks to the slab.
    :func:`block_write_path` asked what ``_slot_steps`` asks it, with
    the place the programs are told: the one the K/V leaves lie in."""
    names = _kv_names(state)
    return block_write_path(
        state, state[names[0]][0].sharding if names else None, n)


def slot_state_path(params, state):
    """``"kernel"``, ``"xla"`` or None (no such block): how the step
    programs that :func:`slot_fns` builds for ``state`` (arrays) take
    the retention blocks' fixed state through the chip.
    ``blocks.state_path`` asked what ``_slot_steps`` asks it, with the
    place the programs are told: the one the fixed state lies in."""
    return blocks.state_path(params, state, state["lengths"].sharding)


def dispatch_program(fn, default):
    """The instrumented program name of a dispatch callable — the
    per-dispatch attribution key the request ledger records
    (``observe/reqledger.py``). ``instrument()`` stamps
    ``program_name`` on every wrapped slot program (live, sharded and
    paged alike); raw callables (a chaos monkeypatch, a bare jit) fall
    back to the call-family ``default`` so attribution never raises."""
    return getattr(fn, "program_name", default)


# -- dispatched-work accounting (observe/servescope.py) -----------------------

def admit_waste(bucket, lens, rows):
    """Token decomposition of ONE admission dispatch: ``lens`` live
    prompt/tail lengths prefilled into ``bucket``-position rows, the
    group padded to ``rows`` rows with duplicates. Returns
    ``(live, bucket_pad, group_dup)`` token counts — ONE definition
    for the serving goodput observatory and its tests, owned by the
    module that shapes the dispatch."""
    lens = [int(n) for n in lens]
    live = sum(lens)
    pad = sum(int(bucket) - n for n in lens)
    dup = (int(rows) - len(lens)) * int(bucket)
    return live, pad, dup


def span_overshoot_tokens(lens, span, chunk):
    """Masked attended positions PAST each live slot's sequence across
    one chunked decode dispatch: every lane-step attends ``span``
    positions, a slot at length ``n`` is live to ``n + i`` at step
    ``i`` — the rest is span-tile overshoot (exact zeros by the
    masking contract, but dispatched work all the same). Exact sum of
    ``max(0, span - (n + i))`` over ``i in 1..chunk`` per slot, in
    closed form."""
    span = int(span)
    chunk = int(chunk)
    total = 0
    for n in lens:
        d = span - int(n)
        k = min(chunk, max(0, d - 1))
        total += k * d - k * (k + 1) // 2
    return total


def page_overshoot_tokens(lens, pages, page_size, chunk):
    """The paged twin of :func:`span_overshoot_tokens`: each live slot
    gathers ``pages`` pages (``pages * page_size`` positions) per
    step, live to its sequence length — the rest is page-bucket
    overshoot (scratch rows and tail positions of partially-filled
    pages)."""
    return span_overshoot_tokens(lens, int(pages) * int(page_size),
                                 chunk)


def tile_pad_tokens(lens, page_size, chunk):
    """The fused-kernel residual: the paged-attention kernel
    (ops/paged_attention.py) walks only each slot's LIVE pages, so the
    span/page overshoot of the gather formulations is structurally
    zero — what remains is the dead tail of the last partial page,
    ``ceil((n + 1) / page_size) * page_size - (n + 1)`` lanes per
    slot-step for a slot live to ``n`` (position ``n`` itself is
    attended: append precedes attend). Exact sum over ``i in
    1..chunk`` with the slot live to ``n + i - 1`` at step ``i``."""
    ps = int(page_size)
    chunk = int(chunk)
    total = 0
    for n in lens:
        for i in range(1, chunk + 1):
            live = int(n) + i
            total += -(-live // ps) * ps - live
    return total


# -- tensor-parallel decode (Megatron-style weight sharding) ------------------

def _repack_block(blk, heads):
    """Host-side repack of one block into head-major layouts the TP
    specs can shard: qkv (E, 3E) → (E, 3, H, D) so each device owns
    whole heads (a flat column shard would give device 0 all the Q
    columns), out-proj (E, E) → (H, D, E) row-sharded by head."""
    embed = blk["wqkv"].shape[0]
    head_dim = embed // heads
    return dict(
        blk,
        wqkv=blk["wqkv"].reshape(embed, 3, heads, head_dim),
        bqkv=blk["bqkv"].reshape(3, heads, head_dim),
        wout=blk["wout"].reshape(heads, head_dim, embed),
    )


def _tp_specs(n_blocks, axis):
    """PartitionSpec pytree for the repacked params under ``axis``:
    whole heads and FFN columns shard; norms and biases that are added
    AFTER a psum stay replicated."""
    from jax.sharding import PartitionSpec as P

    block = {
        "ln1_w": P(), "ln1_b": P(),
        "wqkv": P(None, None, axis, None),
        "bqkv": P(None, axis, None),
        "wout": P(axis, None, None),
        "bout": P(),
        "ln2_w": P(), "ln2_b": P(),
        "w1": P(None, axis), "b1": P(axis),
        "w2": P(axis, None), "b2": P(),
    }
    return {"blocks": [dict(block) for _ in range(n_blocks)],
            "lnf_w": P(), "lnf_b": P(),
            "head": P(None, axis)}


def _tp_local_qkv(blk, x):
    """(B, S, E) → q, k, v each (B, S, h_local, D) from the device's
    head slice of the repacked qkv projection."""
    from veles_tpu.parallel.transformer_step import _ln

    h = _ln(x, blk["ln1_w"], blk["ln1_b"])
    qkv = jnp.einsum("bse,eihd->bsihd", h, blk["wqkv"]) + blk["bqkv"]
    return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]


def make_tp_generate(mesh, heads, n_tokens, axis="model"):
    """Tensor-parallel greedy decoding over ``mesh``'s ``axis``: every
    device holds a head slice of each attention block, a column/row
    slice of each FFN, and a vocab slice of the head — activations are
    replicated, the two per-block matmul reductions ``psum`` over ICI
    (the Megatron inference recipe). The KV cache shards over heads, so
    per-device cache HBM scales with H/n.

    Returns ``run(params, embed_table, prompt_tokens) -> tokens``; the
    params are the standard ``init_transformer_params`` pytree (repacked
    and sharded internally). Requires ``heads`` and the FFN hidden dim
    divisible by the axis size."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from veles_tpu.parallel.transformer_step import _ln

    n = mesh.shape[axis]

    def tp_mlp(blk, x):
        # the shared _mlp with the TP reduction injected: w1
        # col-sharded, w2 row-sharded, psum completes the contraction
        return _mlp(blk, x, reduce=lambda y: lax.psum(y, axis))

    def device_step(params, embed_table, cache, logits):
        """One decode step on each device's shard (inside shard_map)."""
        tok = jnp.argmax(logits, axis=-1)
        x = embed_table[tok][:, None, :]
        length = cache["length"]
        max_len = cache["k"].shape[2]
        mask = (jnp.arange(max_len) <= length)[None, None, None, :]
        new_k, new_v = cache["k"], cache["v"]
        for i, blk in enumerate(params["blocks"]):
            q, k, v = _tp_local_qkv(blk, x)
            new_k = lax.dynamic_update_slice(
                new_k, k[None].astype(new_k.dtype), (i, 0, length, 0, 0))
            new_v = lax.dynamic_update_slice(
                new_v, v[None].astype(new_v.dtype), (i, 0, length, 0, 0))
            # the SAME cache-attend the single-device decode_step runs
            att = _cache_attend(q, _positions_last(new_k[i]),
                                _positions_last(new_v[i]), mask)
            # row-sharded out-projection: psum completes the contraction
            out = lax.psum(
                jnp.einsum("bqhd,hde->bqe", att.astype(x.dtype),
                           blk["wout"]), axis)
            x = x + out + blk["bout"]
            x = tp_mlp(blk, x)
        local_logits = _ln(x[:, 0], params["lnf_w"], params["lnf_b"]) \
            @ params["head"]
        logits = lax.all_gather(local_logits, axis, axis=1, tiled=True)
        return {"k": new_k, "v": new_v, "length": length + 1}, logits, tok

    def device_run(params, embed_table, prompt_x, cache):
        # prefill on the local head slice (full causal attention)
        batch, t, embed = prompt_x.shape
        x = prompt_x
        ks, vs = [], []
        for blk in params["blocks"]:
            q, k, v = _tp_local_qkv(blk, x)
            ks.append(k)
            vs.append(v)
            att = jax.nn.dot_product_attention(q, k, v, is_causal=True)
            out = lax.psum(
                jnp.einsum("bshd,hde->bse", att.astype(x.dtype),
                           blk["wout"]), axis)
            x = x + out + blk["bout"]
            x = tp_mlp(blk, x)
        local_logits = _ln(x[:, -1], params["lnf_w"], params["lnf_b"]) \
            @ params["head"]
        logits = lax.all_gather(local_logits, axis, axis=1, tiled=True)
        cache = {
            "k": lax.dynamic_update_slice(
                cache["k"], jnp.stack(ks).astype(cache["k"].dtype),
                (0, 0, 0, 0, 0)),
            "v": lax.dynamic_update_slice(
                cache["v"], jnp.stack(vs).astype(cache["v"].dtype),
                (0, 0, 0, 0, 0)),
            "length": jnp.int32(t),
        }

        def body(carry, _):
            cache, logits = carry
            cache, logits, tok = device_step(params, embed_table, cache,
                                             logits)
            return (cache, logits), tok

        (cache, logits), toks = lax.scan(body, (cache, logits), None,
                                         length=n_tokens)
        return jnp.swapaxes(toks, 0, 1)

    cache_spec = P(None, None, None, axis, None)
    param_specs = None  # built on first call (needs n_blocks)
    # the jitted program is memoized in the closure: jax.jit keys on
    # the callable's IDENTITY, and a fresh shard_map wrapper per run()
    # call would re-trace every generate (retrace.local-jit-dispatch)
    tp_fn = None

    def run(params, embed_table, prompt_tokens):
        nonlocal param_specs, tp_fn
        if isinstance(params["head"], dict):
            raise ValueError(
                "tensor-parallel decode takes unquantized params (the "
                "int8 tier is single-device serving; TP shards bf16)")
        n_blocks = len(params["blocks"])
        embed = embed_table.shape[1]
        head_dim = embed // heads
        if heads % n or (params["blocks"][0]["w1"].shape[1] % n) \
                or (embed_table.shape[0] % n):
            raise ValueError(
                "tensor-parallel decode needs heads (%d), ffn hidden "
                "(%d) and vocab (%d) divisible by the %r axis size %d"
                % (heads, params["blocks"][0]["w1"].shape[1],
                   embed_table.shape[0], axis, n))
        packed = {"blocks": [_repack_block(blk, heads)
                             for blk in params["blocks"]],
                  "lnf_w": params["lnf_w"], "lnf_b": params["lnf_b"],
                  "head": params["head"]}
        if param_specs is None:
            param_specs = _tp_specs(n_blocks, axis)
        batch, t = prompt_tokens.shape
        cache = init_kv_cache(n_blocks, batch, t + n_tokens, heads,
                              head_dim, dtype=embed_table.dtype)
        prompt_x = embed_table[prompt_tokens]
        cache_specs = {"k": cache_spec, "v": cache_spec,
                       "length": P()}
        # the TABLE is replicated (every device embeds the full token
        # vector); the VOCAB sharding lives in params["head"], whose
        # local logits all_gather back to full width
        if tp_fn is None:
            tp_fn = jax.jit(shard_map(
                device_run, mesh=mesh,
                in_specs=(param_specs, P(), P(), cache_specs),
                out_specs=P()))
        # place the shards explicitly (shard_map would otherwise
        # require pre-sharded inputs for non-replicated specs)
        packed = jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            packed, param_specs)
        table_sharded = jax.device_put(
            embed_table, NamedSharding(mesh, P()))
        cache = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(
                    mesh, cache_spec if a.ndim == 5 else P())), cache)
        return tp_fn(packed, table_sharded, prompt_x, cache)

    return run


# -- mesh-sharded slot serving (layout path) ----------------------------------
#
# The continuous-batching engine above goes multi-chip by LAYOUT, not by
# a second implementation: the params shard tensor-parallel over the
# mesh's ``model`` axis, the slot KV slab shards over its HEADS dim,
# and the ONE copy of the slot math (slot_admit_many / slot_step /
# slot_step_many) runs unchanged — XLA's SPMD partitioner splits the
# sharded matmuls and the head-sharded cache ops along the operand
# shardings and inserts the psum/all-gather collectives. Token streams
# stay identical to the single-chip engine (the collectives only
# reassociate reductions, below token granularity — the same contract
# the TP generate tests pin). One compiled program exists per
# (bucket, group, layout): jit specializes on operand shardings, so the
# instrument() compile counters and the dispatch-count CI hooks keep
# working per layout. docs/sharded_serving.md is the recipe.
#
# Known layout cost vs the hand-written make_tp_generate partition: the
# fused qkv matrix (E, 3E) shards by FLAT columns, whose chunk
# boundaries straddle the q/k/v and head boundaries — the partitioner
# then reshards the (small) qkv activation around the per-head
# reshape/split instead of handing each device whole heads. Fixing it
# needs the head-major repack _repack_block does, i.e. a repacked
# variant of the shared sublayer math — a measured follow-on, not a
# spec change (tracked in docs/sharded_serving.md Limits).

def validate_slot_mesh(mesh, heads, params, embed_table, axis="model"):
    """Fail a bad serving mesh at build time with an error naming the
    offending dimension — never as an opaque partitioner error from
    inside the first admit dispatch."""
    n = dict(mesh.shape).get(axis, 1)
    if n <= 1:
        return n
    blk = params["blocks"][0]
    w1 = blk["w1"]["q8"] if isinstance(blk["w1"], dict) else blk["w1"]
    ffn_hidden = w1.shape[1]
    vocab = embed_table.shape[0]
    if heads % n or ffn_hidden % n or vocab % n:
        raise ValueError(
            "sharded slot serving needs heads (%d), ffn hidden (%d) "
            "and vocab (%d) divisible by the %r axis size %d"
            % (heads, ffn_hidden, vocab, axis, n))
    return n


def slot_param_specs(params, axis="model"):
    """PartitionSpec pytree (same structure as ``params``) for
    tensor-parallel slot serving: attention qkv/FFN-up columns and the
    vocab head shard over ``axis``, out-proj/FFN-down rows shard over
    ``axis``, norms and post-reduction biases replicate. int8-quantized
    leaves (``{"q8", "scale"}``) shard the payload like the float
    matrix; per-output-column scales follow their columns."""
    from jax.sharding import PartitionSpec as P

    def mat(leaf, spec, scale_spec):
        if isinstance(leaf, dict):
            return {"q8": spec, "scale": scale_spec}
        return spec

    blocks = []
    for blk in params["blocks"]:
        specs = {
            "ln1_w": P(), "ln1_b": P(),
            "wqkv": mat(blk["wqkv"], P(None, axis), P(axis)),
            "bqkv": P(axis),
            "wout": mat(blk["wout"], P(axis, None), P()),
            "bout": P(),
            "ln2_w": P(), "ln2_b": P(),
            "w1": mat(blk["w1"], P(None, axis), P(axis)),
            "b1": P(axis),
            "w2": mat(blk["w2"], P(axis, None), P()),
            "b2": P(),
        }
        blocks.append(specs)
    return {"blocks": blocks, "lnf_w": P(), "lnf_b": P(),
            "head": mat(params["head"], P(None, axis), P(axis))}


def slot_state_specs(n_blocks, quantized=False, axis="model"):
    """PartitionSpec pytree mirroring the slot state: each block's KV
    leaves (and the int8 tier's scales) shard over their HEADS dim,
    control leaves (lengths/logits/req_key/step) replicate."""
    from jax.sharding import PartitionSpec as P

    specs = {"lengths": P(), "logits": P(), "req_key": P(),
             "step": P()}
    # (S, H·D, T), split on head boundaries (validate_slot_mesh:
    # heads divide by the axis); the int8 tier's (S, H, D, T)
    kv = P(None, axis, None, None) if quantized else P(None, axis, None)
    specs.update(k=(kv,) * n_blocks, v=(kv,) * n_blocks)
    if quantized:
        scale = P(None, axis, None)             # (S, H, T)
        specs.update(k_scale=(scale,) * n_blocks,
                     v_scale=(scale,) * n_blocks)
    return specs


def shard_slot_tree(tree, mesh, specs):
    """``device_put`` a pytree into ``mesh`` under a matching spec
    pytree (fresh placement — callers moving LIVE state between
    layouts use ``parallel/reshard.reshard``, which rides collectives
    and is measured)."""
    from jax.sharding import NamedSharding

    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: not isinstance(x, (dict, list, tuple)))
    return jax.device_put(tree, shardings)


def shard_slot_params(params, embed_table, heads, mesh, axis="model"):
    """Place decode params + embed table into the serving layout:
    params tensor-parallel over ``axis``, table replicated. Returns
    ``(params, embed_table)``; validates divisibility first."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    blocks.require_gpt2(params, "tensor-parallel serving (mesh=)",
                        tier="mesh")
    validate_slot_mesh(mesh, heads, params, embed_table, axis=axis)
    params = shard_slot_tree(params, mesh, slot_param_specs(params, axis))
    return params, jax.device_put(embed_table, NamedSharding(mesh, P()))


# -- AOT wire format (veles_tpu/aot/) -----------------------------------------
#
# jax.export's flatbuffer schema cannot serialize extended PRNG-key
# dtypes (key<fry>), so every program crossing the AOT artifact boundary
# carries the slot state's ``req_key`` leaf — and the admit path's
# ``req_keys`` operand — as raw uint32 key DATA. ``wrap_key_data``/
# ``key_data`` are bit-level reinterpretations, so wire-format streams
# stay bit-identical to the live programs' (tests/test_aot.py pins it).
# One copy of the convention here, next to the state definition; the
# paged state (parallel/kv_pool.py) shares the leaf name so the same
# helpers serve both engines.

def wire_slot_state(state):
    """Slot/paged state with the ``req_key`` leaf as raw uint32 data —
    the calling convention of every exported slot program."""
    import jax

    return dict(state, req_key=jax.random.key_data(state["req_key"]))


def unwire_slot_state(state):
    """Invert :func:`wire_slot_state`: re-wrap the raw key data into
    the typed PRNG keys the live jit surface expects."""
    import jax

    return dict(state,
                req_key=jax.random.wrap_key_data(state["req_key"]))
