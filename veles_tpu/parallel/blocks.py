"""The block's sublayers, by the block's kind: the model seam.

Every language-model path of the repo (the plain full forward of
``transformer_step._forward``, the prompt forward of
``decode._prefill_forward`` and the decode chunk of
``decode._slot_steps``) runs a block as the same five sublayers:

    q, rows = kind.project(arch, blk, x, heads, positions)
    att     = kind.attend_prompt(...)  |  kind.attend_cached(...)
                                       |  kind.step(...)  (fixed state)
    x       = kind.out(blk, x, att)
    x, load = ffn(arch, blk, x, live)
    logits  = head(arch, params, x, embed_table)

``kind`` is the BLOCK's (:func:`block_kinds`): a model declares a kind
for each of its blocks (``Arch.layers``), and a model whose blocks are
all alike names the one. ``rows`` is what the block keeps of a
sequence, and the kind declares the leaves that hold it
(``kind.leaves``), of two sorts: a row a POSITION (``(S, row, T)``:
what attention reads back) or a FIXED state a slot (``(S, ...)``, no
position axis: what a recurrence carries). The slot state is built,
written and read over whatever leaves the blocks' kinds declare.

How an architecture travels: with the parameters. ``params["arch"]``
is an :class:`Arch`, a static node of the pytree (no array in it; it
is part of every jitted program's key). A tree without one is GPT-2's
block throughout (``GPT2``, kind ``"mha"``): pre-LN LayerNorm, fused
biased qkv, equal Q and K/V heads, no position encoding, GELU MLP.
``"mla"`` is latent attention with RoPE (RMSNorm, no bias): a position
leaves ONE row ``[c | k_rope]`` of ``kv_rank + rope_dim`` values for
all heads; the prompt attends expanded (``k_nope``, ``v`` made from
``c``), a decode step absorbed (the query goes into the latent space
and attends the rows as they lie), the same numbers. ``"gqa"`` is
grouped-query attention (``heads`` query heads over ``kv_heads`` K/V
heads, RMSNorm over each head's q and k, RoPE): GPT-2's ``k``/``v``
leaves at ``kv_heads * head_dim`` a position, a group's query heads
attending their one K/V head as it lies. ``"conv"`` is a gated short
convolution: it keeps no row a position but the last
``conv_taps - 1`` gated inputs of a slot, which a step reads and
rewrites. ``"ret"`` is power retention (``ops/retention.py``):
grouped-query attention's projection and a gate a K/V head, the score
``(q·k)² / d`` under a learned decay in place of the softmax, so that
a slot's whole past is a fixed float32 state a K/V head and no row a
position at all. ``"swa"`` and ``"nope"`` are grouped-query attention
with no head norm: ``"swa"`` rotates q and k (RoPE) and attends the last
``Arch.window`` positions, the query's own included, from leaves
``k_ring``/``v_ring`` that are a RING of that many positions a slot
(position ``p`` at ``p mod window``), so that its cache stops growing
at the window; ``"nope"`` takes no position encoding and attends the
whole sequence from rows a position (``k_all``/``v_all``), its output
gated elementwise where the block has a ``wgate`` leaf. ``"kda"`` is
the gated delta rule with a decay a key channel (``ops/delta_rule.py``,
Kimi Delta Attention): q, k and v through short depthwise convolutions,
a float32 state a head that each position decays, corrects along its
key and reads with its query, so that a slot's past is that state and
the convolutions' last inputs, and no row a position. The
feed-forward kind is read off the block's own leaves: ``w1`` GELU MLP,
``w_gate`` SwiGLU, ``router`` the routed experts of ``ops/moe.py``
(with shared experts where the block has a ``shared`` leaf, their sum
scaled by ``Arch.shared_scale``).

A block is sequential, ``x + attn(n(x))`` and then ``x + ffn(n'(x))``
of that sum, unless its model says ``Arch.parallel``: one norm of the
block's input, which attention and the feed-forward both read, ``x +
attn(n(x)) + ffn(n(x))`` (:func:`block_rest`). ``Arch.norm`` names the
norm of every sublayer and of the head: RMSNorm, or LayerNorm with a
gain and no bias.

The named scopes are the ones the per-layer readers know
(``attn.qkv``, ``attn.attend``, ``attn.out``, ``mlp``, ``head``,
``cache.append``), with the kinds' own nested under them (``mla.q``,
``mla.kv``, ``mla.rope``, ``mla.absorb``, ``gqa.norm``, ``gqa.rope``,
``conv.in``, ``conv.mix``, ``conv.out``, ``cache.state``, ``ret.gate``,
``ret.phi``, ``ret.state``, ``ret.chunk``, ``swa.rope``, ``swa.ring``,
``nope.attend``, ``cache.ring``, ``gqa.gate``, ``kda.gate``, ``kda.conv``,
``kda.chunk``, ``kda.state``, ``kda.norm``, ``moe.*``).
"""

import dataclasses

import jax
import jax.numpy as jnp

from veles_tpu.ops import delta_rule, moe, retention, slab_attention
from veles_tpu.ops.attention import (attention, grouped_attention,
                                     prompt_path)
from veles_tpu.ops.quant import int8_cache_attend, matmul_any


@dataclasses.dataclass(frozen=True)
class Arch:
    """What the blocks' sublayers are. Sizes the leaves' shapes do not
    give are stated here; ``heads`` travels as every caller passes it."""
    #: the kind of each block (``KINDS``): a tuple of names, one a
    #: block in the model's order, or the one name of a model whose
    #: blocks are all alike
    layers: object = "mha"
    #: ``Arch(attention="mla")`` says ``layers="mla"``: the one kind of
    #: every block, as a model with a single kind states it
    attention: dataclasses.InitVar[str] = None
    eps: float = 1e-5
    # grouped-query attention (RoPE's ``rope_theta`` is below)
    kv_heads: int = 0
    #: the width of a head of ``"swa"``/``"nope"`` where it is not the
    #: hidden size over the heads (0: it is)
    head_dim: int = 0
    #: taps of a block's depthwise convolutions (``"conv"``; ``"kda"``'s
    #: of q, k and v)
    conv_taps: int = 3
    # power retention: the degree of the score (2 is the one there is)
    power: int = 2
    # latent attention
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    rope_theta: float = 10000.0
    # routed experts
    top_k: int = 0
    route_scale: float = 1.0
    #: added to the sum that normalises the chosen experts' scores
    route_eps: float = 0.0
    #: (first, count) of the experts held here; None: all of them
    held: tuple = None
    #: prompt tokens a block takes at once in an admission (rows of a
    #: group beyond that go through in turn); 0: the whole group
    prefill_tokens: int = 0
    #: prompt positions (rows x bucket) one admission takes at most; a
    #: larger group of a bucket is admitted in several (0: no limit)
    admit_tokens: int = 0
    #: the least admission bucket: a shorter prompt pads to it, so its
    #: bucket's programs are never built (0: the power of two at or
    #: above the prompt's length)
    prompt_bucket: int = 0
    #: positions a ``"swa"`` block attends, the query's own included
    window: int = 0
    #: one norm of the block's input read by attention and the
    #: feed-forward alike, both added to the input
    parallel: bool = False
    #: the norm of every sublayer and of the head: ``"rms"`` or
    #: ``"layer"`` (LayerNorm with a gain and no bias)
    norm: str = "rms"
    #: what the shared experts' output is scaled by (1 / their number
    #: where a model averages them)
    shared_scale: float = 1.0

    def __post_init__(self, attention):
        layers = self.layers if attention is None else attention
        if not isinstance(layers, str):
            layers = tuple(layers)
        object.__setattr__(self, "layers", layers)


jax.tree_util.register_static(Arch)
GPT2 = Arch()


def arch_of(params):
    return params.get("arch", GPT2)


def expert_blocks(params):
    """Indices of the blocks whose feed-forward is routed experts."""
    return [i for i, blk in enumerate(params["blocks"]) if "router" in blk]


def expert_plan(params, tokens):
    """``(path, slices)``: the tiling the routed experts' products take
    (``ops/moe.expert_plan``) in a program whose feed-forward sees
    ``tokens`` tokens at once, and the slices of their inner width an
    expert is taken in (1: whole); None for a model without routed
    experts."""
    routed = expert_blocks(params)
    if not routed:
        return None
    experts = params["blocks"][routed[0]]["experts"]
    path, _, sliced = moe.expert_plan(tokens * arch_of(params).top_k,
                                      experts)
    return path, experts["w_gate"].shape[-1] // sliced


def attend_path(params, state, sharding):
    """How a decode step over the slot state ``state`` (arrays,
    tracers or shapes), whose K/V leaves lie as ``sharding`` says
    (None: nobody knows), attends the cache: ``"kernel"`` where every
    block that attends has a kernel over ragged lengths
    (``attend_ragged``) and its rule takes it
    (``ops/slab_attention.use_slab_kernel``, read off the platform,
    the leaves' type and shape and the place), else ``"xla"``
    (``attend_cached`` over the rectangular window). The ONE question:
    ``decode._slot_steps`` asks it when a program is traced for a
    place, the decoder asks it of the state it holds for its books."""
    rows = [kind for kind in set(block_kinds(
        arch_of(params), len(params["blocks"]))) if not kind.fixed]
    if rows and all(hasattr(kind, "attend_ragged") for kind in rows) \
            and "k_scale" not in state \
            and all(slab_attention.use_slab_kernel(state[kind.leaf][0],
                                                   sharding)
                    for kind in rows):
        return "kernel"
    return "xla"


def prompt_attend_path(params, batch, t, heads):
    """How a block of grouped heads with no head norm (``"swa"``,
    ``"nope"``) attends ``batch`` prompts of ``t`` positions at once
    (``ops/attention.prompt_path``: ``"kernel"`` or ``"xla"``); None
    for a model without such a block. Asked by the trace of an admit
    program, through ``grouped_attention``, and by the decoder for its
    books."""
    for blk, kind in zip(params["blocks"], block_kinds(
            arch_of(params), len(params["blocks"]))):
        if issubclass(kind, Global):
            return prompt_path(batch, t, heads,
                               blk["wq"].shape[-1] // heads)
    return None


def state_path(params, state, sharding):
    """How a decode step takes the fixed state of the retention blocks
    through the chip (``ops/retention.state_path``: ``"kernel"`` or
    ``"xla"``), ``state`` the slot state (arrays, tracers or shapes)
    whose fixed leaves lie as ``sharding`` says; None for a model
    without such a block. Asked like :func:`attend_path`: by the trace
    of a step program, and by the decoder for its books."""
    held = state.get("fixed", {}).get(Retention.leaf)
    if not held:
        return None
    return retention.state_path(held[0], sharding)


def prompt_chunks(params, rows, t):
    """Chunks of ``ops/delta_rule.CHUNK`` positions that an admission
    of ``rows`` prompts of ``t`` positions runs through the delta
    rule's chunked form, over its ``"kda"`` blocks; None for a model
    without such a block."""
    blocks = sum(1 for kind in block_kinds(arch_of(params),
                                           len(params["blocks"]))
                 if kind is DeltaRule)
    if not blocks:
        return None
    return blocks * rows * -(-t // delta_rule.CHUNK)


def require_gpt2(params, what, lacks=None, tier=None):
    """Refuse by name what only GPT-2's block has yet; ``lacks`` says
    what the tier would need for another kind, and a kind that knows
    what ``tier`` (``"paged"``, ``"prefix"``, ``"int8"``, ``"mesh"``)
    lacks for it says so itself (``Kind.lacks``)."""
    arch = arch_of(params)
    if arch != GPT2 or expert_blocks(params):
        names = sorted(set(layer_names(arch, len(params["blocks"]))))
        own = ["%r: %s" % (name, KINDS[name].lacks[tier])
               for name in names if tier in _kind(name).lacks]
        raise ValueError(
            "%s is built for GPT-2's block (fused qkv, k/v leaves of "
            "heads x head_dim, GELU MLP) and this model declares "
            "%s%s: that tier has no such kind yet%s%s" % (
                what, kinds_said(arch),
                " with routed experts" if expert_blocks(params) else "",
                " (%s)" % lacks if lacks else "",
                "".join("; for kind " + text for text in own)))


# -- norms ---------------------------------------------------------------------

def _ln(x, w, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def rms_norm(x, w, eps):
    """``x / rms(x) * w``, the mean square in float32."""
    wide = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(wide * wide, -1, keepdims=True) + eps)
    return (wide * scale * w.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, w, eps):
    """``(x - mean x) / sqrt(var x + eps) * w``, no bias, the statistics
    in float32."""
    wide = x.astype(jnp.float32)
    centred = wide - jnp.mean(wide, -1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(centred * centred, -1, keepdims=True)
                          + eps)
    return (centred * scale * w.astype(jnp.float32)).astype(x.dtype)


def norm(arch, x, w):
    """The model's norm (``Arch.norm``) of ``x`` with the gain ``w``."""
    if arch.norm == "layer":
        return layer_norm(x, w, arch.eps)
    return rms_norm(x, w, arch.eps)


# -- position encoding ---------------------------------------------------------

def rope(x, positions, theta):
    """Rotate the pairs ``(2i, 2i+1)`` of ``x`` (..., T, [H,] R) by
    ``positions (..., T) * theta ** (-2i / R)`` (interleaved pairs)."""
    r = x.shape[-1]
    freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = positions.astype(jnp.float32)[..., None] * freq
    if x.ndim == angle.ndim + 1:            # a heads axis before R
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    wide = x.astype(jnp.float32)
    even, odd = wide[..., 0::2], wide[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


# -- attention against the cache -----------------------------------------------

def _positions_last(x):
    """``(..., T, H, D)`` -> ``(..., H, D, T)``: rows of K/V as
    ``_block_qkv`` makes them, in the slab's order."""
    return jnp.moveaxis(x, -3, -1)


def _quantize_kv(x):
    """Per-(batch, position, head) symmetric int8: (..., D) ->
    (int8 (..., D), f32 scale (...,)). The quantization the cache
    stores; one copy for prefill and decode appends."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0]


def _cache_attend(q, k_all, v_all, mask, tail=None, scale=None):
    """Attention of query tokens against the cache prefix, f32 softmax:
    ONE copy of the math for the single-device and tensor-parallel
    decode paths (the TP guarantee of token-identity depends on it).
    K/V come head-major with positions minor, ``(B, H, D, T)``: the
    order the slot slab holds them in (:func:`init_slot_state`), so the
    slot step hands over its window as it lies. A caller whose cache
    is positions-major hands a transposed view (:func:`_positions_last`),
    which XLA folds into the dots. ``tail`` is ``(k, v, mask)`` of more
    positions that lie in another buffer (the slot chunk's staged
    columns): one softmax over both, no copy that joins them. The
    int8-cache variant lives in ``ops/quant.int8_cache_attend`` (same
    order, dequantization fused into the dots). ``scale`` defaults to
    ``1 / sqrt(D)``."""
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    parts = [(k_all, v_all, mask)] + ([tail] if tail is not None else [])
    # q (B,1,H,D) x cache K (B,H,D,T) -> (B,H,1,T)
    scores = [jnp.where(m, jnp.einsum(
        "bqhd,bhdk->bhqk", q, k.astype(q.dtype),
        preferred_element_type=jnp.float32) * scale, -1e30)
        for k, _, m in parts]
    p = jax.nn.softmax(jnp.concatenate(scores, axis=-1)
                       if tail is not None else scores[0], axis=-1)
    out, at = None, 0
    for (_, v, _), s in zip(parts, scores):
        part = jnp.einsum(
            "bhqk,bhdk->bqhd", p[..., at:at + s.shape[-1]].astype(q.dtype),
            v.astype(q.dtype), preferred_element_type=jnp.float32)
        out = part if out is None else out + part
        at += s.shape[-1]
    return out


# -- GPT-2's sublayers (the first instance) ------------------------------------
#
# The helpers keep their names: the paged pool, the tensor-parallel
# decode and ``generate`` run GPT-2's block through them directly.

def _block_qkv(blk, x, heads):
    """Pre-LN qkv projection: (B, T, E) -> three (B, T, H, D)."""
    batch, t, embed = x.shape
    with jax.named_scope("attn.qkv"):
        h = _ln(x, blk["ln1_w"], blk["ln1_b"])
        qkv = matmul_any(h, blk["wqkv"]) + blk["bqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (batch, t, heads, embed // heads)
        return q.reshape(shape), k.reshape(shape), v.reshape(shape)


def _mlp(blk, x, reduce=None):
    """Pre-LN residual gelu MLP. ``reduce`` completes a sharded
    contraction (tensor-parallel decode passes a psum; ``b2`` is added
    AFTER it, so it stays replicated) — one copy of the math for the
    single-device and TP paths alike. The products route through
    ``matmul_any`` so the int8 serving tier (``ops/quant.py``) shares
    this exact sublayer math."""
    with jax.named_scope("mlp"):
        h = _ln(x, blk["ln2_w"], blk["ln2_b"])
        y = matmul_any(jax.nn.gelu(matmul_any(h, blk["w1"]) + blk["b1"]),
                       blk["w2"])
        if reduce is not None:
            y = reduce(y)
        return x + y + blk["b2"]


def _head(params, x):
    """Final layer norm + vocab projection."""
    with jax.named_scope("head"):
        return matmul_any(_ln(x, params["lnf_w"], params["lnf_b"]),
                          params["head"])


class Kind:
    """What every kind of block says of what it keeps of a sequence.
    ``fixed`` is False where it keeps a row a position (attention: the
    slab's ``(S, row, T)`` leaves, staged by a chunk and read back as
    a window) and True where it keeps a fixed state a slot (``(S,
    ...)`` leaves that a step rewrites: ``step`` in place of
    ``attend_cached``)."""
    fixed = False
    #: the first of the leaf names it declares (kinds that share it
    #: share their leaves' tuples in the slot state: ``leaf_ordinals``)
    leaf = "k"
    #: what a tier that is built on GPT-2's leaves lacks for this kind,
    #: by the tier's key (:func:`require_gpt2`), where the kind has
    #: more to say than the tier's own refusal
    lacks = {}
    #: whether its rows a position lie in a ring (position ``p`` at
    #: ``p mod`` the leaf's length) and not at ``p``
    ring = False

    @staticmethod
    def positions(arch, max_len):
        """The length of its leaves' position axis in a slab of
        ``max_len`` positions a slot."""
        return max_len

    @staticmethod
    def keep(arch, rows, live):
        """What of a prompt's ``rows`` the cache keeps: of rows a
        position, all of them (the slab is written to the bucket's
        end, and a sequence's own appends overwrite the padding)."""
        return rows

    @staticmethod
    def put(leaf, slots, value):
        """A fixed-state ``leaf`` (S, ...) with the state of ``slots``
        (B,) set whole to ``value`` (B, ...): an admission's write."""
        return leaf.at[slots].set(value)

    @classmethod
    def prompt(cls, arch, blk, q, rows, live):
        """The prompt's attend and what the cache keeps of it, ``(att,
        kept)``; ``kept`` None: :func:`block_forward` asks ``keep`` once
        the block's rest is done. A kind whose one pass over the prompt
        gives both (``"kda"``: the state after each row's true length
        is the chunk scan's carry) says so here."""
        return cls.attend_prompt(arch, blk, q, rows), None


class FusedQKV(Kind):
    """``"mha"``: K and V rows of ``heads * head_dim`` a position
    (the int8-KV tier: int8 rows and a scale a head)."""

    @staticmethod
    def leaves(arch, heads, head_dim, dtype, quantized=False):
        """``{leaf name: (a position's row shape, dtype)}``; for a
        kind with ``fixed`` state, a slot's."""
        if not quantized:
            return dict.fromkeys(("k", "v"), ((heads * head_dim,), dtype))
        leaves = dict.fromkeys(("k", "v"), ((heads, head_dim), jnp.int8))
        leaves.update(dict.fromkeys(("k_scale", "v_scale"),
                                    ((heads,), jnp.float32)))
        return leaves

    @staticmethod
    def project(arch, blk, x, heads, positions):
        q, k, v = _block_qkv(blk, x, heads)
        return q, {"k": k, "v": v}

    @staticmethod
    def columns(state, rows):
        """New rows ``(..., T, H, D)`` as the state's leaves hold them:
        ``{leaf name: (..., H·D, T)}`` in the leaves' dtype, and for
        the int8-KV tier the quantized rows ``(..., H, D, T)`` with
        their scales ``(..., H, T)``. One copy for the admission
        scatter and the per-step appends."""
        k, v = rows["k"], rows["v"]
        if "k_scale" not in state:
            dtype = state["k"][0].dtype
            folded = k.shape[:-3] + (-1, k.shape[-3])   # (..., H·D, T)
            return {"k": _positions_last(k).astype(dtype).reshape(folded),
                    "v": _positions_last(v).astype(dtype).reshape(folded)}
        out = {}
        for name, val in (("k", k), ("v", v)):
            q8, scale = _quantize_kv(val)           # (..,T,H,D), (..,T,H)
            out[name] = _positions_last(q8)
            out[name + "_scale"] = jnp.swapaxes(scale, -2, -1)
        return out

    @staticmethod
    def attend_prompt(arch, blk, q, rows):
        """Full causal attention over the prompt — the SAME gated op
        the training forward uses (flash kernel for prompts >= 4096).
        With a quantized cache the prompt attention still runs on the
        exact K/V; only the CACHED copies are rounded (decode steps
        then attend against what was stored, like every later token)."""
        with jax.named_scope("attn.attend"):
            att = attention(q, rows["k"], rows["v"], causal=True)
            return att.reshape(att.shape[:2] + (-1,))

    @staticmethod
    def attend_cached(arch, blk, q, read, staged, mask, mask_staged):
        """One query a slot against the window ``read`` and the chunk's
        staged columns, both as the leaves hold them; the masks are
        bool ``(S, 1, 1, T)``, for the int8-KV tier f32 addends
        ``(S, T)``. Returns ``(S, 1, H·D)``."""
        slots, _, heads, head_dim = q.shape
        with jax.named_scope("attn.attend"):
            if "k_scale" in read:
                # python float (weak type): `q * inv_sqrt` must NOT
                # promote a bf16 q to f32 — that would kill the
                # fallback path's bf16 compute branch and widen the
                # int8 cache to f32
                att = int8_cache_attend(
                    q * head_dim ** -0.5, read["k"], read["k_scale"],
                    read["v"], read["v_scale"], mask,
                    tail=(staged["k"], staged["k_scale"], staged["v"],
                          staged["v_scale"], mask_staged))
            else:
                # heads unfolded: no byte moves, D is whole tiles
                def apart(leaf):
                    return leaf.reshape((slots, heads, -1, leaf.shape[-1]))

                att = _cache_attend(
                    q, apart(read["k"]), apart(read["v"]), mask,
                    tail=(apart(staged["k"]), apart(staged["v"]),
                          mask_staged))
            return att.reshape(slots, 1, -1)

    @staticmethod
    def attend_ragged(q, leaves, staged, lengths, span, mask_staged):
        """:meth:`attend_cached` where :func:`attend_path` says
        ``kernel``: one query a slot against the first ``lengths[s]``
        positions (at most ``span``, static) of the block's ``leaves``,
        taken whole from where they lie, and the chunk's staged
        columns, in one softmax (``ops/slab_attention.py``). No window
        is sliced and no mask over it built. Returns ``(S, 1, H·D)``."""
        slots, _, heads, _ = q.shape

        def apart(leaf):
            return leaf.reshape((slots, heads, -1, leaf.shape[-1]))

        with jax.named_scope("attn.attend"):
            att = slab_attention.join_tail(
                q, slab_attention.slab_attend(q, leaves["k"], leaves["v"],
                                              lengths, span),
                apart(staged["k"]), apart(staged["v"]), mask_staged)
            return att.reshape(slots, 1, -1)

    @staticmethod
    def out(blk, x, att):
        with jax.named_scope("attn.out"):
            return x + matmul_any(att.astype(x.dtype), blk["wout"]) \
                + blk["bout"]


class Latent(Kind):
    """``"mla"``: one row ``[c | k_rope]`` a position for all
    heads. Leaves of a block: ``attn_norm``, ``wq_a``, ``q_norm``,
    ``wq_b`` (q_rank, H·(nope+rope)), ``wkv_a`` (E, kv_rank+rope),
    ``kv_norm``, ``wkv_b`` (kv_rank, H·(nope+v)), ``wout``."""
    leaf = "kv"

    @staticmethod
    def leaves(arch, heads, head_dim, dtype, quantized=False):
        return {"kv": ((arch.kv_rank + arch.rope_dim,), dtype)}

    @staticmethod
    def project(arch, blk, x, heads, positions):
        """``positions`` (B, T): where each token stands in its
        sequence. Returns ``((q_nope, q_rope), {"kv": (B, T, W)})``."""
        batch, t, _ = x.shape
        with jax.named_scope("attn.qkv"):
            h = rms_norm(x, blk["attn_norm"], arch.eps)
            with jax.named_scope("mla.q"):
                q = rms_norm(h @ blk["wq_a"], blk["q_norm"], arch.eps) \
                    @ blk["wq_b"]
                q = q.reshape(batch, t, heads, -1)
                q_nope, q_rope = q[..., :arch.nope_dim], \
                    q[..., arch.nope_dim:]
            with jax.named_scope("mla.kv"):
                kv = h @ blk["wkv_a"]
                c = rms_norm(kv[..., :arch.kv_rank], blk["kv_norm"],
                             arch.eps)
            with jax.named_scope("mla.rope"):
                q_rope = rope(q_rope, positions, arch.rope_theta)
                k_rope = rope(kv[..., arch.kv_rank:], positions,
                              arch.rope_theta)
            return (q_nope, q_rope), {
                "kv": jnp.concatenate([c, k_rope], -1)}

    @staticmethod
    def columns(state, rows):
        """``(..., T, W)`` -> ``{"kv": (..., W, T)}``."""
        return {"kv": jnp.swapaxes(rows["kv"], -2, -1)
                .astype(state["kv"][0].dtype)}

    @staticmethod
    def _up(arch, blk, heads):
        """``wkv_b`` as (kv_rank, H, nope + v)."""
        return blk["wkv_b"].reshape(arch.kv_rank, heads, -1)

    @staticmethod
    def attend_prompt(arch, blk, q, rows):
        """Expanded: ``k_nope`` and ``v`` made from ``c`` for every
        head, ``k_rope`` shared by the heads, scores over
        ``nope + rope``."""
        q_nope, q_rope = q
        heads = q_nope.shape[2]
        with jax.named_scope("attn.attend"):
            c = rows["kv"][..., :arch.kv_rank]
            k_rope = rows["kv"][..., None, arch.kv_rank:]
            up = jnp.einsum("btc,chd->bthd", c,
                            Latent._up(arch, blk, heads))
            k = jnp.concatenate(
                [up[..., :arch.nope_dim],
                 jnp.broadcast_to(k_rope, k_rope.shape[:2] + (heads,)
                                  + k_rope.shape[3:])], -1)
            att = attention(jnp.concatenate([q_nope, q_rope], -1), k,
                            up[..., arch.nope_dim:], causal=True)
            return att.reshape(att.shape[:2] + (-1,))

    @staticmethod
    def attend_cached(arch, blk, q, read, staged, mask, mask_staged):
        """Absorbed: the query goes into the latent space
        (``q_nope . W^K``), the heads attend the one row a position as
        queries of one sequence, and ``W^V`` lifts what they gather.
        The weighted sum runs over the whole row; its ``k_rope`` tail
        is dropped (an eighth more work, and no cut of the window)."""
        q_nope, q_rope = q                          # (S, 1, H, ·)
        slots, _, heads, _ = q_nope.shape
        up = Latent._up(arch, blk, heads)
        with jax.named_scope("attn.attend"):
            with jax.named_scope("mla.absorb"):
                q_lat = jnp.einsum("sqhn,chn->sqhc", q_nope,
                                   up[..., :arch.nope_dim])
            # the heads stand where _cache_attend has its queries, the
            # one row where it has its one head: (S, H, 1, W)
            q_all = jnp.concatenate([q_lat.astype(q_rope.dtype), q_rope],
                                    -1).reshape(slots, heads, 1, -1)
            got = _cache_attend(
                q_all, read["kv"][:, None], read["kv"][:, None], mask,
                tail=(staged["kv"][:, None], staged["kv"][:, None],
                      mask_staged),
                scale=(arch.nope_dim + arch.rope_dim) ** -0.5)
            with jax.named_scope("mla.absorb"):
                att = jnp.einsum(
                    "shc,chv->shv",
                    got[:, :, 0, :arch.kv_rank].astype(q_rope.dtype),
                    up[..., arch.nope_dim:])
            return att.reshape(slots, 1, -1)

    @staticmethod
    def out(blk, x, att):
        with jax.named_scope("attn.out"):
            return x + att.astype(x.dtype) @ blk["wout"]


def _grouped_cached(arch, q, k, v, k_staged, v_staged, mask, mask_staged):
    """Grouped heads' attend of one query a slot, ``q`` (S, 1, H, D),
    over the leaves' window ``k``/``v`` (S, H_kv·D, T) and the staged
    columns (S, H_kv·D, n) as they lie: ``(S, 1, H·D)``."""
    slots, _, heads, head_dim = q.shape
    groups = arch.kv_heads

    def apart(leaf):
        return leaf.reshape((slots, groups, -1, leaf.shape[-1]))

    # (S, 1, H, D) -> (S, H // groups, groups, D)
    q_all = jnp.swapaxes(
        q.reshape(slots, groups, heads // groups, head_dim), 1, 2)
    att = _cache_attend(q_all, apart(k), apart(v), mask,
                        tail=(apart(k_staged), apart(v_staged),
                              mask_staged))
    return jnp.swapaxes(att, 1, 2).reshape(slots, 1, -1)


class Grouped(Kind):
    """``"gqa"``: ``heads`` query heads over ``arch.kv_heads`` K/V
    heads (query head ``i`` attends K/V head ``i // (heads //
    kv_heads)``), RMSNorm over the ``head_dim`` of each head's q and
    k, RoPE. K and V rows of ``kv_heads * head_dim`` a position, in
    GPT-2's leaves. Leaves of a block: ``attn_norm``, ``wq`` (E, H·D),
    ``wk``/``wv`` (E, H_kv·D), ``q_norm``/``k_norm`` (D,), ``wout``
    (H·D, E); no bias."""

    @staticmethod
    def leaves(arch, heads, head_dim, dtype, quantized=False):
        return FusedQKV.leaves(arch, arch.kv_heads, head_dim, dtype)

    @staticmethod
    def normed_qkv(arch, blk, x, heads, positions):
        """``(h, q, k, v)``: the normed input, and its three
        projections with the head norms and RoPE on q and k. Under
        ``attn.qkv`` (the caller's)."""
        batch, t, _ = x.shape
        h = rms_norm(x, blk["attn_norm"], arch.eps)
        q = (h @ blk["wq"]).reshape(batch, t, heads, -1)
        k = (h @ blk["wk"]).reshape(batch, t, arch.kv_heads, -1)
        v = (h @ blk["wv"]).reshape(batch, t, arch.kv_heads, -1)
        with jax.named_scope("gqa.norm"):
            q = rms_norm(q, blk["q_norm"], arch.eps)
            k = rms_norm(k, blk["k_norm"], arch.eps)
        with jax.named_scope("gqa.rope"):
            q = rope(q, positions, arch.rope_theta)
            k = rope(k, positions, arch.rope_theta)
        return h, q, k, v

    @staticmethod
    def project(arch, blk, x, heads, positions):
        with jax.named_scope("attn.qkv"):
            _, q, k, v = Grouped.normed_qkv(arch, blk, x, heads,
                                            positions)
            return q, {"k": k, "v": v}

    @staticmethod
    def columns(state, rows):
        """GPT-2's, of rows that are values of their own. Fused with
        the norm and the rotation that made ``k``, the chunk program's
        staged column takes a slots-minor layout, and XLA's TPU
        compiler then fails a RET_CHECK on the chunk's block write
        ("The shape doesn't match when replacing", at every slab shape
        tried, compiled off the chip for a v5e): the barrier keeps the
        rows' making and their staging apart. 64 KB a step."""
        return FusedQKV.columns(state, jax.lax.optimization_barrier(rows))

    @staticmethod
    def attend_prompt(arch, blk, q, rows):
        """Causal attention over the prompt, the K/V heads as they
        are (``ops.attention`` takes fewer K/V heads than queries)."""
        with jax.named_scope("attn.attend"):
            att = attention(q, rows["k"], rows["v"], causal=True)
            return att.reshape(att.shape[:2] + (-1,))

    @staticmethod
    def attend_cached(arch, blk, q, read, staged, mask, mask_staged):
        """One query a head a slot against the window and the staged
        columns. A group's query heads stand where ``_cache_attend``
        has its queries and the K/V heads where it has its heads: each
        K/V row is read once, as it lies, for the whole group."""
        with jax.named_scope("attn.attend"):
            return _grouped_cached(arch, q, read["k"], read["v"],
                                   staged["k"], staged["v"], mask,
                                   mask_staged)

    @staticmethod
    def out(blk, x, att):
        with jax.named_scope("attn.out"):
            return x + att.astype(x.dtype) @ blk["wout"]


class ShortConv(Kind):
    """``"conv"``: a gated short convolution. ``B, C, u = split3(h .
    W_in)``; ``z_t = sum_j w_j * (B * u)_{t - (taps - 1) + j}``
    (depthwise, causal, zeros before the sequence, no bias); ``out =
    (C * z) . W_out``. It keeps no row a position: its one leaf is the
    FIXED state of a slot, the last ``taps - 1`` gated inputs ``B *
    u`` side by side, oldest first: ``(S, (taps - 1) * E)``. Leaves of
    a block: ``attn_norm``, ``w_in`` (E, 3E), ``conv_w`` (taps, E),
    ``w_out`` (E, E)."""
    fixed = True
    leaf = "conv"

    @staticmethod
    def leaves(arch, heads, head_dim, dtype, quantized=False):
        return {"conv": (((arch.conv_taps - 1) * heads * head_dim,),
                         dtype)}

    @staticmethod
    def project(arch, blk, x, heads, positions):
        """``(C, {"conv": B * u})``, both ``(B, T, E)``."""
        # the operator's norm under ``conv.in`` too: the compiler fuses
        # it into the projection, and the scope table names a fusion by
        # the scope most of its instructions carry
        with jax.named_scope("attn.qkv"), jax.named_scope("conv.in"):
            h = rms_norm(x, blk["attn_norm"], arch.eps)
            b, c, u = jnp.split(h @ blk["w_in"], 3, axis=-1)
        with jax.named_scope("attn.attend"), jax.named_scope("conv.mix"):
            return c, {"conv": b * u}

    @staticmethod
    def _taps(blk, inputs):
        """``sum_j w_j * inputs[j]`` in float32, ``inputs`` oldest
        first."""
        taps = blk["conv_w"].astype(jnp.float32)
        return sum(taps[j] * part.astype(jnp.float32)
                   for j, part in enumerate(inputs))

    @staticmethod
    def attend_prompt(arch, blk, q, rows):
        """The whole sequence: tap ``j`` sees the gated input shifted
        ``taps - 1 - j`` positions back, zeros before the start."""
        gated = rows["conv"]
        t, back = gated.shape[1], arch.conv_taps - 1
        with jax.named_scope("attn.attend"), jax.named_scope("conv.mix"):
            padded = jnp.pad(gated, ((0, 0), (back, 0), (0, 0)))
            z = ShortConv._taps(blk, [padded[:, j:j + t]
                                      for j in range(back + 1)])
            return (q.astype(jnp.float32) * z).astype(q.dtype)

    @staticmethod
    def keep(arch, rows, live):
        """The state after each row's LAST position: the gated inputs
        at ``length - (taps - 1) .. length - 1`` of a right-padded
        row (``live`` (B, T) marks its own positions; None: all of
        them), zeros where the prompt is shorter:
        ``{"conv": (B, (taps - 1) · E)}``."""
        gated = rows["conv"]
        batch, t, _ = gated.shape
        back = arch.conv_taps - 1
        lengths = jnp.full((batch,), t) if live is None \
            else jnp.sum(live, -1)
        at = lengths[:, None] - back + jnp.arange(back)         # (B, back)
        got = jnp.take_along_axis(gated, jnp.maximum(at, 0)[..., None],
                                  axis=1)
        got = jnp.where((at >= 0)[..., None], got, 0)
        return {"conv": got.reshape(batch, -1)}

    @staticmethod
    def columns(state, rows):
        return {"conv": rows["conv"].astype(state["conv"][0].dtype)}

    @staticmethod
    def step(arch, blk, q, rows, fixed, active, sharding=None):
        """One new position a slot: ``(att (S, 1, E), fixed)``. The
        taps see what the slot carries (``fixed["conv"]`` (S,
        (taps-1)·E)) and the new gated input; the state rolls by one,
        and a lane that is not ``active`` keeps what it had.
        ``sharding`` (where the state lies) is for kinds whose step
        has a kernel to choose."""
        held, gated = fixed["conv"], rows["conv"][:, 0]
        back = arch.conv_taps - 1
        with jax.named_scope("attn.attend"), jax.named_scope("conv.mix"):
            z = ShortConv._taps(
                blk, jnp.split(held, back, axis=-1) + [gated])
            att = (q[:, 0].astype(jnp.float32) * z).astype(q.dtype)
        with jax.named_scope("cache.append"), \
                jax.named_scope("cache.state"):
            rolled = jnp.concatenate(
                [held[:, gated.shape[-1]:], gated.astype(held.dtype)], -1)
            return att[:, None], {
                "conv": jnp.where(active[:, None], rolled, held)}

    @staticmethod
    def out(blk, x, att):
        with jax.named_scope("attn.out"), jax.named_scope("conv.out"):
            return x + att.astype(x.dtype) @ blk["w_out"]


class Retention(Kind):
    """``"ret"``: power retention of degree 2 (``ops/retention.py``).
    The projection is grouped-query attention's (``Grouped.normed_qkv``:
    the same leaves ``attn_norm``, ``wq``, ``wk``, ``wv``, ``q_norm``,
    ``k_norm``, ``wout``, the same scopes) plus a gate a K/V head,
    ``log g = log sigmoid(h . wg + bg)`` in float32 (``wg`` (E, H_kv),
    ``bg`` (H_kv,) float32). It keeps no row a position: a slot's
    state is ``S`` ``(H_kv, D, D')`` and ``z`` ``(H_kv, D')``, float32
    whatever the serving type (the recurrence sums thousands of
    terms), ``D' = retention.features(D)``."""
    fixed = True
    leaf = "S"
    lacks = {
        "paged": "a page holds the k/v rows of some positions and the "
                 "page table says which; this kind keeps no row a "
                 "position, only a state a slot, which no table indexes",
        "prefix": "a cached prefix is its pages; this kind's prefix is "
                  "the state after it (S and z, 34 MB a slot a layer at "
                  "head_dim 128), which would have to be snapshot when "
                  "the prefix ends and copied into the slot on a hit",
        "int8": "quantize_params knows wqkv, w1, w2 and the int8 cache "
                "k/v rows; this kind has wq, wk, wv, wg and a float32 "
                "state that a recurrence sums into, which int8 rows "
                "cannot hold",
        "mesh": "slot_state_specs shards k/v leaves over heads; this "
                "kind's S and z would shard over K/V heads, and the "
                "state's kernel (ops/retention.py) is a bare "
                "pallas_call that lies on one device",
    }

    @staticmethod
    def leaves(arch, heads, head_dim, dtype, quantized=False):
        if arch.power != 2:
            raise ValueError(
                "power retention of degree %r: the feature map of "
                "ops/retention.py is degree 2's (phi(q).phi(k) = "
                "(q.k)^2); another degree has another map and another "
                "state" % (arch.power,))
        wide = retention.features(head_dim)
        return {"S": ((arch.kv_heads, head_dim, wide), jnp.float32),
                "z": ((arch.kv_heads, wide), jnp.float32)}

    @staticmethod
    def project(arch, blk, x, heads, positions):
        """``(q, {"k", "v", "log_g"})``: ``log_g`` (B, T, H_kv)
        float32, what a position leaves of the state before it."""
        with jax.named_scope("attn.qkv"):
            h, q, k, v = Grouped.normed_qkv(arch, blk, x, heads,
                                            positions)
            with jax.named_scope("ret.gate"):
                gate = jnp.einsum(
                    "bte,eg->btg", h, blk["wg"],
                    preferred_element_type=jnp.float32) + blk["bg"]
                return q, {"k": k, "v": v,
                           "log_g": jax.nn.log_sigmoid(gate)}

    @staticmethod
    def attend_prompt(arch, blk, q, rows):
        """The chunked form over right-padded rows (causal: a row's
        padding changes nothing before it)."""
        with jax.named_scope("attn.attend"), jax.named_scope("ret.chunk"):
            return retention.prompt(q, rows["k"], rows["v"],
                                    rows["log_g"])

    @staticmethod
    def keep(arch, rows, live):
        """The state after each row's TRUE length (``live`` (B, T)
        marks its own positions; None: all of them)."""
        with jax.named_scope("attn.attend"), jax.named_scope("ret.state"):
            return retention.state_after(rows["k"], rows["v"],
                                         rows["log_g"], live)

    @staticmethod
    def columns(state, rows):
        return {name: rows[name].astype(state[name][0].dtype)
                for name in ("S", "z")}

    @staticmethod
    def put(leaf, slots, value):
        """Row by row, each a write of its own where the leaf lies. A
        scatter of rows this size (34 MB at ``head_dim`` 128) compiles
        to a select over the WHOLE leaf, every slot's state read and
        written to admit one (compiled for a v5e: 545 MB a layer)."""
        def one(j, leaf):
            at = (slots[j],) + (0,) * (leaf.ndim - 1)
            return jax.lax.dynamic_update_slice(
                leaf, jax.lax.dynamic_slice_in_dim(value, j, 1, 0), at)

        return jax.lax.fori_loop(0, slots.shape[0], one, leaf)

    @staticmethod
    def step(arch, blk, q, rows, fixed, active, sharding=None):
        """One new position a slot through the recurrence: ``(att (S,
        1, H.D), fixed)``; a lane that is not ``active`` keeps its
        state. The state's write is part of ``ret.state``."""
        with jax.named_scope("attn.attend"):
            att, held, norm = retention.step(
                q[:, 0], rows["k"][:, 0], rows["v"][:, 0],
                rows["log_g"][:, 0], fixed["S"], fixed["z"], active,
                sharding)
            return att.astype(q.dtype)[:, None], {"S": held, "z": norm}

    out = Grouped.out


def _gate_of(q):
    """``(q, gate)`` of what a ``"nope"`` block's ``project`` gave: a
    gated block's query travels with its gate's pre-activation (None:
    the block has no gate)."""
    return q if isinstance(q, tuple) else (q, None)


def _gated(att, gate):
    """``att ⊙ sigmoid(gate)`` in float32, in ``att``'s type (``att``
    as it is where ``gate`` is None)."""
    if gate is None:
        return att
    with jax.named_scope("attn.out"), jax.named_scope("gqa.gate"):
        return (att.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))).astype(att.dtype)


class Global(Kind):
    """``"nope"``: grouped-query attention (``heads`` query heads over
    ``arch.kv_heads`` K/V heads) with no head norm and no position
    encoding, over the whole sequence: rows a position in leaves of its
    own (``names``), ``kv_heads * head_dim`` wide. Leaves of a block:
    ``attn_norm``, ``wq`` (E, H·D), ``wk``/``wv`` (E, H_kv·D), ``wout``
    (H·D, E); no bias. A block with a ``wgate`` leaf (E, H·D) gates its
    attention's output elementwise before ``wout``, ``att ⊙ sigmoid(h .
    wgate)`` (``gqa.gate``): its query travels with the gate's
    pre-activation, ``(q, gate)``, from ``project`` to the attend."""
    leaf = "k_all"
    names = ("k_all", "v_all")
    #: the scope of its attend, inside ``attn.attend``
    scope = "nope.attend"
    rotate = False
    lacks = {
        "paged": "pages hold k/v rows of every block alike; this model's "
                 "window blocks keep a ring of their own length beside "
                 "its global rows, which no page table indexes",
        "prefix": "a cached prefix is its pages; a window block's ring "
                  "holds only the last window of positions, which a "
                  "prefix would have to carry as a snapshot",
        "int8": "quantize_params knows wqkv, w1, w2 and GPT-2's k/v "
                "leaves; this kind has wq, wk, wv, and a ring beside "
                "its rows, which the int8 cache does not lay out",
        "mesh": "slot_state_specs shards the k/v leaves over heads; "
                "this kind's leaves and the window's rings would "
                "shard over K/V heads, and the routed experts are "
                "held here whole",
    }

    @classmethod
    def leaves(cls, arch, heads, head_dim, dtype, quantized=False):
        return dict.fromkeys(
            cls.names, ((arch.kv_heads * (arch.head_dim or head_dim),),
                        dtype))

    @classmethod
    def project(cls, arch, blk, x, heads, positions):
        batch, t, _ = x.shape
        with jax.named_scope("attn.qkv"):
            h = norm(arch, x, blk["attn_norm"])
            q = (h @ blk["wq"]).reshape(batch, t, heads, -1)
            k = (h @ blk["wk"]).reshape(batch, t, arch.kv_heads, -1)
            v = (h @ blk["wv"]).reshape(batch, t, arch.kv_heads, -1)
            if cls.rotate:
                with jax.named_scope("swa.rope"):
                    q = rope(q, positions, arch.rope_theta)
                    k = rope(k, positions, arch.rope_theta)
            if "wgate" in blk:
                with jax.named_scope("gqa.gate"):
                    q = (q, h @ blk["wgate"])
            return q, dict(zip(cls.names, (k, v)))

    @classmethod
    def columns(cls, state, rows):
        """GPT-2's fold of new rows ``(..., T, H_kv, D)`` into ``(...,
        H_kv·D, T)``, behind ``Grouped.columns``' barrier (the same
        compiler fault waits for a rotated ``k``)."""
        rows = jax.lax.optimization_barrier(rows)
        dtype = state[cls.names[0]][0].dtype
        return {name: _positions_last(rows[name]).astype(dtype).reshape(
            rows[name].shape[:-3] + (-1, rows[name].shape[-3]))
            for name in cls.names}

    @classmethod
    def window(cls, arch):
        """Positions a query attends, its own included; 0: all before
        it."""
        return 0

    @classmethod
    def attend_prompt(cls, arch, blk, q, rows):
        """Causal attention over the prompt (within the window), the
        K/V heads as they are (``ops/attention.grouped_attention``)."""
        k, v = cls.names
        q, gate = _gate_of(q)
        with jax.named_scope("attn.attend"), jax.named_scope(cls.scope):
            att = grouped_attention(q, rows[k], rows[v],
                                    window=cls.window(arch))
            att = att.reshape(att.shape[:2] + (-1,))
        return _gated(att, gate)

    @classmethod
    def attend_cached(cls, arch, blk, q, read, staged, mask, mask_staged):
        """Grouped heads over the leaves' window and the staged columns
        (``_grouped_cached``), ``mask`` saying which of the window's
        positions each slot sees."""
        k, v = cls.names
        q, gate = _gate_of(q)
        with jax.named_scope("attn.attend"), jax.named_scope(cls.scope):
            att = _grouped_cached(arch, q, read[k], read[v], staged[k],
                                  staged[v], mask, mask_staged)
        return _gated(att, gate)

    @classmethod
    def attend_ragged(cls, q, leaves, staged, lengths, span, mask_staged,
                      ring=None):
        """:meth:`attend_cached` where :func:`attend_path` says
        ``kernel``: FusedQKV's, each K/V head's rows read once for its
        group of query heads (``ops/slab_attention.slab_attend``), a
        ring's entries as ``ring`` says; the staged columns, a K/V head
        repeated for its group, joined in one softmax."""
        q, gate = _gate_of(q)
        slots, _, heads, head_dim = q.shape
        k, v = cls.names
        groups = leaves[k].shape[1] // head_dim

        def apart(leaf):
            return jnp.repeat(leaf.reshape(slots, groups, head_dim, -1),
                              heads // groups, axis=1)

        with jax.named_scope("attn.attend"), jax.named_scope(cls.scope):
            att = slab_attention.join_tail(
                q, slab_attention.slab_attend(q, leaves[k], leaves[v],
                                              lengths, span, ring=ring,
                                              scope=cls.scope),
                apart(staged[k]), apart(staged[v]), mask_staged)
            att = att.reshape(slots, 1, -1)
        return _gated(att, gate)

    out = Grouped.out


class Windowed(Global):
    """``"swa"``: ``"nope"``'s projection with RoPE on q and k
    (``swa.rope``, at ``Arch.rope_theta``), attending the last
    ``Arch.window`` positions, the query's own included. Its leaves are
    a RING of ``min(window, max_len)`` positions a slot: position ``p``
    lies at ``p mod`` that length, a chunk's block is written there
    (split where it wraps: ``ops/slab_write.py``), and a step sees an
    entry only while it holds a position inside the window
    (``decode.ring_visible``)."""
    leaf = "k_ring"
    names = ("k_ring", "v_ring")
    scope = "swa.ring"
    rotate = True
    ring = True

    @staticmethod
    def positions(arch, max_len):
        return min(arch.window, max_len)

    @classmethod
    def window(cls, arch):
        return arch.window


def _l2(x):
    """``x / |x|`` over its last axis in float32 (``+ 1e-6`` under the
    root)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


class DeltaRule(Kind):
    """``"kda"``: the gated delta rule with a decay a key channel (Kimi
    Delta Attention, ``ops/delta_rule.py``) over ``heads`` heads of
    ``Arch.head_dim``, q, k and v alike. From ``h = norm(x)``: the three
    streams ``h . w_qkv`` (E, 3·H·D), each channel through a causal
    depthwise convolution of ``Arch.conv_taps`` taps (``conv_w``
    (taps, 3·H·D), no bias) and SiLU, q and k of unit length a head, q
    scaled by ``1/sqrt(D)``; the log-decays ``g = -exp(A_log) ·
    softplus((h . w_fa) . w_fb + dt_bias)`` a channel (``A_log`` (H,),
    ``dt_bias`` (H·D,) float32) and the write strength ``b =
    write_scale · sigmoid(h . w_beta)`` a head; the state's
    answer ``o`` RMS-normed a head (``o_norm`` (D,)), gated by
    ``sigmoid((h . w_ga) . w_gb)``, then ``wout`` (H·D, E).

    It keeps no row a position. A slot's FIXED state is two leaves: the
    delta rule's ``kda_S`` ``(H, D, D)`` float32 (4 MB a layer at 64
    heads of 128) and the convolutions' ``kda_conv``, the last ``taps -
    1`` inputs of the three streams side by side, oldest first, ``((taps
    - 1) · 3·H·D,)`` in the serving type. Scopes: ``kda.gate`` (the
    decays, the write strength and the output gate's projection, in
    ``attn.qkv``), ``kda.conv`` (the convolutions, their state's roll and
    the norms of q and k, in ``attn.qkv``), ``kda.chunk`` (the prompt's
    chunked form) and ``kda.state`` (a step's pass of the state) in
    ``attn.attend``, ``kda.norm`` (the gated head norm, in
    ``attn.out``)."""
    fixed = True
    leaf = "kda_S"
    #: the write strength's scale: ``b`` in (0, 2), so that ``I - b k
    #: kᵀ`` may have a negative eigenvalue (Solar Open 2's
    #: ``kda_allow_neg_eigval``, the one model of this kind)
    write_scale = 2.0
    lacks = {
        "paged": "a page holds the k/v rows of some positions and the "
                 "page table says which; this kind keeps no row a "
                 "position, only a state a slot (the delta rule's S and "
                 "the convolutions' tails), which no table indexes",
        "prefix": "a cached prefix is its pages; this kind's prefix is "
                  "the state after it (S, 4 MB a slot a layer at 64 "
                  "heads of 128, and the convolutions' last inputs), "
                  "which would have to be snapshot when the prefix ends "
                  "and copied into the slot on a hit",
        "int8": "quantize_params knows wqkv, w1, w2 and the int8 cache "
                "k/v rows; this kind has w_qkv, the low-rank decay and "
                "gate, and a float32 state that the delta rule corrects "
                "at every position, which int8 rows cannot hold",
        "mesh": "slot_state_specs shards k/v leaves over heads and "
                "gives a fixed leaf no spec; this kind's S and "
                "convolution tails would shard over heads",
    }

    @staticmethod
    def leaves(arch, heads, head_dim, dtype, quantized=False):
        d = arch.head_dim or head_dim
        return {"kda_S": ((heads, d, d), jnp.float32),
                "kda_conv": (((arch.conv_taps - 1) * 3 * heads * d,),
                             dtype)}

    @classmethod
    def project(cls, arch, blk, x, heads, positions):
        """``(gate, rows)``: the output gate's pre-activation (B, T,
        H·D) and ``{"conv": the three streams before their convolution
        (B, T, 3·H·D), "g": log-decays (B, T, H, D) float32, "beta":
        (B, T, H) float32}``."""
        batch, t, _ = x.shape
        with jax.named_scope("attn.qkv"):
            h = norm(arch, x, blk["attn_norm"])
            mixed = h @ blk["w_qkv"]
            with jax.named_scope("kda.gate"):
                rate = jnp.einsum(
                    "btr,rc->btc", h @ blk["w_fa"], blk["w_fb"],
                    preferred_element_type=jnp.float32) + blk["dt_bias"]
                g = -jnp.exp(blk["A_log"])[:, None] * jax.nn.softplus(
                    rate.reshape(batch, t, heads, -1))
                beta = cls.write_scale * jax.nn.sigmoid(jnp.einsum(
                    "bte,eh->bth", h, blk["w_beta"],
                    preferred_element_type=jnp.float32))
                gate = (h @ blk["w_ga"]) @ blk["w_gb"]
        return gate, {"conv": mixed, "g": g, "beta": beta}

    @staticmethod
    def _streams(arch, blk, inputs, heads):
        """q, k, v (..., H, D) in the inputs' type from the taps'
        ``inputs`` (..., 3·H·D), oldest first: the convolution and SiLU
        in float32, q and k of unit length, q scaled."""
        taps = blk["conv_w"].astype(jnp.float32)
        mixed = jax.nn.silu(sum(taps[j] * part.astype(jnp.float32)
                                for j, part in enumerate(inputs)))
        q, k, v = (part.reshape(part.shape[:-1] + (heads, -1))
                   for part in jnp.split(mixed, 3, axis=-1))
        q = _l2(q) * q.shape[-1] ** -0.5
        dtype = inputs[-1].dtype
        return q.astype(dtype), _l2(k).astype(dtype), v.astype(dtype)

    @staticmethod
    def _finish(arch, blk, o, gate):
        """The answer ``o`` (..., H, D) RMS-normed a head and gated:
        (..., H·D) in the gate's type."""
        with jax.named_scope("attn.out"), jax.named_scope("kda.norm"):
            normed = rms_norm(o.astype(jnp.float32), blk["o_norm"],
                              arch.eps)
            return (normed.reshape(gate.shape) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(gate.dtype)

    @classmethod
    def prompt(cls, arch, blk, q, rows, live):
        """The whole right-padded prompt in one pass: ``(att, kept)``,
        ``kept`` the state and the convolutions' tails after each row's
        TRUE length (``live`` (B, T); None: all of it)."""
        mixed = rows["conv"]
        t, back = mixed.shape[1], arch.conv_taps - 1
        heads = rows["beta"].shape[-1]
        with jax.named_scope("attn.qkv"), jax.named_scope("kda.conv"):
            padded = jnp.pad(mixed, ((0, 0), (back, 0), (0, 0)))
            qq, kk, vv = cls._streams(
                arch, blk, [padded[:, j:j + t] for j in range(back + 1)],
                heads)
            tail = ShortConv.keep(arch, {"conv": mixed}, live)
        with jax.named_scope("attn.attend"), jax.named_scope("kda.chunk"):
            y, held = delta_rule.prompt(qq, kk, vv, rows["g"],
                                        rows["beta"], live)
        return cls._finish(arch, blk, y, q), {"kda_S": held,
                                              "kda_conv": tail["conv"]}

    @staticmethod
    def columns(state, rows):
        return {name: rows[name].astype(state[name][0].dtype)
                for name in ("kda_S", "kda_conv")}

    put = Retention.put

    @classmethod
    def step(cls, arch, blk, q, rows, fixed, active, sharding=None):
        """One new position a slot: ``(att (S, 1, H·D), fixed)``. The
        convolutions see the inputs the slot carries and the new ones,
        and roll by one; the state goes through the chip once
        (``ops/delta_rule.step``). A lane that is not ``active`` keeps
        both as they were."""
        held, new = fixed["kda_conv"], rows["conv"][:, 0]
        back = arch.conv_taps - 1
        heads = rows["beta"].shape[-1]
        with jax.named_scope("attn.qkv"), jax.named_scope("kda.conv"):
            qq, kk, vv = cls._streams(
                arch, blk, jnp.split(held, back, axis=-1) + [new], heads)
            rolled = jnp.concatenate(
                [held[:, new.shape[-1]:], new.astype(held.dtype)], -1)
            conv = jnp.where(active[:, None], rolled, held)
        with jax.named_scope("attn.attend"), jax.named_scope("kda.state"):
            y, state = delta_rule.step(
                qq, kk, vv, rows["g"][:, 0], rows["beta"][:, 0],
                fixed["kda_S"], active)
        att = cls._finish(arch, blk, y, q[:, 0])
        return att[:, None], {"kda_S": state, "kda_conv": conv}

    out = Grouped.out


#: a block's kind by the name a model declares for it (``Arch.layers``)
KINDS = {"mha": FusedQKV, "mla": Latent, "gqa": Grouped,
         "conv": ShortConv, "ret": Retention, "swa": Windowed,
         "nope": Global, "kda": DeltaRule}


def _kind(name):
    if name not in KINDS:
        raise ValueError("no block kind %r (known: %s)"
                         % (name, ", ".join(sorted(KINDS))))
    return KINDS[name]


def layer_names(arch, n_blocks):
    """The name of each of a model's ``n_blocks`` blocks' kind."""
    if isinstance(arch.layers, str):
        return (arch.layers,) * n_blocks
    if len(arch.layers) != n_blocks:
        raise ValueError("the model declares a kind for %d blocks and "
                         "has %d" % (len(arch.layers), n_blocks))
    return arch.layers


def block_kinds(arch, n_blocks):
    """The kind of each of a model's ``n_blocks`` blocks, in order."""
    return tuple(_kind(name) for name in layer_names(arch, n_blocks))


def kinds_said(arch):
    """The model's kinds as a refusal names them, in the words the
    model declared them with."""
    if isinstance(arch.layers, str):
        return "attention=%r" % arch.layers
    return "layers=(%s)" % ", ".join(
        "%d x %r" % (arch.layers.count(name), name)
        for name in sorted(set(arch.layers)))


def leaf_ordinals(kinds):
    """Where each block finds its leaves: the slot state holds, under
    each leaf name, one array for every block whose kind declares the
    name, in the blocks' order, and block ``i``'s are at
    ``leaf_ordinals(kinds)[i]`` of each."""
    return tuple(sum(1 for before in kinds[:i] if before.leaf == kind.leaf)
                 for i, kind in enumerate(kinds))


# -- feed-forward and head -----------------------------------------------------

def _ffn_out(arch, blk, h, live):
    """The feed-forward's output for the normed tokens ``h``:
    ``(y, load)``."""
    if "router" not in blk:
        return moe.swiglu(h, blk), None
    flat = h.reshape(-1, h.shape[-1])
    y, load = moe.expert_layer(
        flat, blk, arch.top_k, arch.route_scale, held=arch.held,
        live=None if live is None else live.reshape(-1),
        eps=arch.route_eps, shared_scale=arch.shared_scale)
    return y.reshape(h.shape), load


def ffn(arch, blk, x, live=None):
    """The block's residual feed-forward, its kind read off the
    block's leaves. ``live`` (B, T) bool marks the tokens that are
    someone's (not padding, not an idle slot): routed experts leave
    the others out. Returns ``(x, load)``, ``load`` the assignments
    per held expert of an expert block and None otherwise."""
    if "w1" in blk:
        return _mlp(blk, x), None
    with jax.named_scope("mlp"):
        y, load = _ffn_out(arch, blk, norm(arch, x, blk["ffn_norm"]), live)
        return x + y, load


def block_rest(arch, blk, kind, x, att, live=None):
    """The block after its attend, ``x`` the block's input and ``att``
    what the attend gave: ``(x, load)`` as :func:`ffn` says. A
    sequential block adds the attention's output and then the
    feed-forward of the sum; a parallel one (``Arch.parallel``) adds
    both to ``x``, the feed-forward reading the attention's norm of
    ``x`` (``attn_norm``: the block has no other)."""
    if not arch.parallel:
        return ffn(arch, blk, kind.out(blk, x, att), live)
    with jax.named_scope("mlp"):
        y, load = _ffn_out(arch, blk, norm(arch, x, blk["attn_norm"]),
                           live)
    return kind.out(blk, x, att) + y, load


def head(arch, params, x, embed_table=None):
    """Final norm and vocabulary projection. A model without a
    ``head`` leaf ties it to the embedding: ``embed_table`` (V, E),
    contracted as it lies."""
    if "lnf_w" in params:
        return _head(params, x)
    with jax.named_scope("head"):
        h = norm(arch, x, params["norm_w"])
        if "head" in params:
            return h @ params["head"]
        return jnp.einsum("...e,ve->...v", h, embed_table)


def block_forward(arch, blk, x, heads, positions, live=None, kind=None):
    """One block (of ``kind``; None: the model's one kind) over whole
    sequences ``x`` (B, T, E): ``(x, rows)``, ``rows`` what the cache
    keeps of them (``kind.keep``). The prompt's path through a block,
    shared by the plain full forward and the admission."""
    if kind is None:
        kind, = block_kinds(arch, 1)
    q, rows = kind.project(arch, blk, x, heads, positions)
    att, kept = kind.prompt(arch, blk, q, rows, live)
    x, _ = block_rest(arch, blk, kind, x, att, live)
    return x, kind.keep(arch, rows, live) if kept is None else kept
