"""Attention ops: flash attention and ring attention (sequence parallel).

No reference counterpart — VELES predates attention (SURVEY §5
"Long-context: absent") — but long context is first-class here. Two tiers:

- ``attention``: single-device fused attention. Uses the Pallas TPU flash
  kernel for real workloads, falling back to ``jax.nn.dot_product_attention``
  (XLA) for small/ragged shapes and non-TPU backends.
- ``ring_attention``: blockwise attention over a ``seq``-sharded mesh axis.
  Each device holds one query block; K/V blocks rotate around the ring via
  ``lax.ppermute`` over ICI while a running online-softmax (m, l, o)
  accumulator absorbs each visiting block — compute overlaps transfer and
  no device ever materializes the full sequence. This is the
  RingAttention/blockwise-parallel pattern; causal masking uses block
  positions so fully-masked pairs still do one cheap fused pass.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from veles_tpu.ops.platform import on_tpu


def attention(q, k, v, causal=False, scale=None):
    """Fused single-device attention. Shapes: (B, T, H, D); a ``v``
    narrower than ``q`` (latent attention: 128 against 192) goes
    through zero-padded, and the output is cut back to its width."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if v.shape[-1] < q.shape[-1]:
        wide = jnp.pad(v, [(0, 0)] * 3 + [(0, q.shape[-1] - v.shape[-1])])
        return attention(q, k, wide, causal, scale)[..., :v.shape[-1]]
    if use_flash(q, k):
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)
        # pallas kernel wants (B, H, T, D)
        out = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, sm_scale=scale)
        return out.transpose(0, 2, 1, 3)
    return jax.nn.dot_product_attention(
        q, k, v, scale=scale, is_causal=causal)


def use_flash(q, k):
    """Whether ``attention`` takes the Pallas flash kernel: on the TPU,
    from 4096 positions on each side, head_dim a multiple of 128.

    The crossover was measured on the v5e before this round (two-length
    device timing, causal, hd=128; no ledger row yet): XLA's attention
    wins below ~4k sequence (0.08 vs 0.34 ms at S=512, 1.38 vs 1.74 ms
    at S=2048); the flash kernel takes over once the S x S score
    materialization dominates (1.06x at S=4096, 1.21x at S=8192). It
    also tiles (T, D) onto (128, 128) MXU blocks, so head_dim must
    divide 128."""
    return (on_tpu() and q.shape[1] >= 4096 and k.shape[1] >= 4096
            and q.shape[-1] % 128 == 0)


#: the most bytes of float32 scores XLA's form of a grouped prompt
#: attention may hold (``B x H x T x T x 4``); past them the splash
#: kernel takes the call on the TPU. At 128 query heads a 256-position
#: row holds 32 MiB of them, a 1,024-position row 512 MiB and an
#: 8,192-position row 32 GiB
SCORE_BYTES = 64 << 20
#: the splash kernel's name in a compiled program (and a trace's ops)
SPLASH_NAME = "splash_mqa_fwd"


def grouped_attention(q, k, v, window=0):
    """Causal attention of ``q`` (B, T, H, D) over ``k``/``v`` (B, T,
    H_kv, D), query head ``i`` on K/V head ``i // (H / H_kv)``; with
    ``window``, the query at ``t`` sees positions ``(t - window, t]``.
    As :func:`prompt_path` says: XLA's form, which holds the scores,
    or the splash kernel (``jax.experimental.pallas.ops.tpu.
    splash_attention``, one MQA call a K/V head and row), which holds
    none."""
    batch, t, heads, head_dim = q.shape
    scale = 1.0 / math.sqrt(head_dim)
    if prompt_path(batch, t, heads, head_dim) == "kernel":
        return _splash(q, k, v, window, scale)
    local = (window - 1, 0) if 0 < window < t else None
    return jax.nn.dot_product_attention(q, k, v, scale=scale,
                                        is_causal=True,
                                        local_window_size=local)


def prompt_path(batch, t, heads, head_dim):
    """``"kernel"`` or ``"xla"``: how :func:`grouped_attention` attends
    ``batch`` prompts of ``t`` positions with ``heads`` query heads of
    ``head_dim``. The kernel on the TPU where XLA's scores would pass
    ``SCORE_BYTES``, ``head_dim`` is whole lane tiles and ``t`` whole
    blocks of 128; XLA's form elsewhere. Read off shapes and the
    platform, so the decoder books it as the program takes it."""
    if on_tpu() and head_dim % 128 == 0 and t % 128 == 0 \
            and batch * heads * t * t * 4 > SCORE_BYTES:
        return "kernel"
    return "xla"


def _splash(q, k, v, window, scale):
    """The splash kernel over every (row, K/V head): the group's query
    heads against their one K/V head, blocks of up to 512 positions,
    blocks the mask leaves empty skipped."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    from veles_tpu.ops.platform import pallas_interpret

    batch, t, heads, head_dim = q.shape
    groups = k.shape[2]
    mask = masks.LocalMask((t, t), (window - 1, 0), 0) \
        if 0 < window < t else masks.CausalMask((t, t))
    block = min(512, t)
    kernel = splash.make_splash_mqa(
        masks.MultiHeadMask([mask] * (heads // groups)),
        block_sizes=splash.BlockSizes(block_q=block, block_kv=block,
                                      block_kv_compute=block),
        head_shards=1, q_seq_shards=1, interpret=pallas_interpret())
    # (B, T, H, D) -> (B, H_kv, H / H_kv, T, D); K/V (B, H_kv, T, D)
    qs = jnp.moveaxis((q * scale).astype(q.dtype).reshape(
        batch, t, groups, heads // groups, head_dim), 1, 3)
    out = jax.vmap(jax.vmap(kernel))(qs, jnp.moveaxis(k, 1, 2),
                                     jnp.moveaxis(v, 1, 2))
    return jnp.moveaxis(out, 3, 1).reshape(q.shape)


def attention_block(x, w_qkv, b_qkv, w_out, b_out, heads, causal,
                    residual=False, precision_level=None):
    """The complete self-attention block — fused qkv projection →
    multi-head attention → out projection (→ residual add) — under the
    SAME engine precision policy as the dense/conv paths (``ops/gemm.py
    compute_operands``): level 0 runs the projections and the attention
    core in bf16 with f32 matmul accumulation (~15% faster forward than
    f32 operands, measured), levels 1/2 keep f32 with HIGH/HIGHEST.
    ONE implementation — the residual included, like ``ffn_block`` —
    serves the graph unit (``nn/attention.py``), its vjp backward, and
    the fused engine — the modes stay bit-identical by construction."""
    from veles_tpu.ops.gemm import compute_operands

    batch, t, embed = x.shape
    head_dim = embed // heads
    (xc, wqkv, wout), precision = compute_operands(
        x, w_qkv, w_out, precision_level=precision_level)
    qkv = lax.dot_general(
        xc, wqkv, (((2,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) + b_qkv
    q, k, v = jnp.split(qkv.astype(xc.dtype), 3, axis=-1)
    shape = (batch, t, heads, head_dim)
    q, k, v = q.reshape(shape), k.reshape(shape), v.reshape(shape)
    if precision is lax.Precision.DEFAULT:
        out = attention(q, k, v, causal=causal)
    else:
        # the accuracy tiers (levels 1/2): jax.nn.dot_product_attention
        # exposes no precision knob, so the core runs as explicit dots
        # carrying the requested HIGH/HIGHEST passes
        out = _precise_attention(q, k, v, causal, precision)
    out = lax.dot_general(
        out.reshape(batch, t, embed).astype(xc.dtype), wout,
        (((2,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) + b_out
    return x + out if residual else out


#: activations usable inside the FFN block. gelu is jax.nn's default
#: tanh approximation — the native runtime (native/src/units.cc FfnUnit)
#: implements the same polynomial so exported packages stay in tolerance.
_FFN_ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "linear": lambda h: h,
}


def ffn_block(x, w1, b1, w2, b2, activation="gelu", residual=True,
              precision_level=None):
    """Position-wise transformer feed-forward block —
    ``act(x @ w1 + b1) @ w2 + b2`` with an optional residual add — under
    the SAME engine precision policy as the attention/dense/conv paths
    (``ops/gemm.py compute_operands``): level 0 runs both projections in
    bf16 with f32 matmul accumulation; the bias adds, activation and
    residual stay f32. ONE implementation serves the graph unit
    (``nn/attention.TokenFFN``), its vjp backward, and the fused engine —
    the modes stay bit-identical by construction.

    No reference counterpart (VELES predates transformers); this extends
    the sequence-model tier the same way SelfAttention does."""
    from veles_tpu.ops.gemm import compute_operands

    act = _FFN_ACTIVATIONS[activation]
    (xc, w1c, w2c), precision = compute_operands(
        x, w1, w2, precision_level=precision_level)
    h = lax.dot_general(
        xc, w1c, (((x.ndim - 1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) + b1
    out = lax.dot_general(
        act(h).astype(xc.dtype), w2c,
        (((h.ndim - 1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) + b2
    return x + out if residual else out


def _precise_attention(q, k, v, causal, precision):
    """Reference-math attention with an explicit lax precision on the
    score and value matmuls (the level-1/2 contract); f32 softmax."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v,
                      precision=precision,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# -- ring attention -----------------------------------------------------------

def _block_attend(q, k, v, scale, mask_value, causal, q_pos, kv_pos):
    """One (q-block x kv-block) pass returning unnormalized (o, m, l):
    o = exp(s - m) @ v row-accumulator, m = row max, l = row sum."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qi = q_pos[:, None]
        ki = kv_pos[None, :]
        s = jnp.where((ki <= qi)[None, None, :, :], s, mask_value)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return o, m, l


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   mask_value=-1e30):
    """Sequence-parallel attention inside shard_map: ``q/k/v`` are the
    LOCAL sequence blocks (B, T_local, H, D); the full sequence is
    ``T_local * axis_size`` long, laid out in ring order along
    ``axis_name``. Returns the local block of the attention output."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    axis_size = lax.axis_size(axis_name)
    my_index = lax.axis_index(axis_name)
    t_local = q.shape[1]
    q_pos = my_index * t_local + jnp.arange(t_local)

    batch, _, heads, _ = q.shape
    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((batch, heads, t_local), mask_value, jnp.float32)
    l = jnp.zeros((batch, heads, t_local), jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def body(carry, step):
        o, m, l, k_blk, v_blk = carry
        src_index = (my_index - step) % axis_size
        kv_pos = src_index * t_local + jnp.arange(t_local)
        o_i, m_i, l_i = _block_attend(q, k_blk, v_blk, scale, mask_value,
                                      causal, q_pos, kv_pos)
        # online-softmax merge of the visiting block
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_i - m_new)
        l = l * alpha + l_i * beta
        o = (o * alpha.transpose(0, 2, 1)[..., None]
             + o_i * beta.transpose(0, 2, 1)[..., None])
        # rotate K/V around the ring (overlaps with next block's compute)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (o, m_new, l, k_blk, v_blk), None

    # lax.scan, not fori_loop: scan is reverse-differentiable, so ring
    # attention works inside jax.grad (ring-parallel TRAINING) at the
    # cost of per-step residuals. The running max starts at mask_value
    # (not -inf): a -inf start makes exp(m - m_new) produce inf*0=nan
    # in the backward pass for fully-masked first blocks.
    (o, m, l, _, _), _ = lax.scan(body, (o, m, l, k, v),
                                  jnp.arange(axis_size))
    l = jnp.maximum(l, 1e-20)  # fully-masked rows (causal first block)
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def make_ring_attention(mesh, axis_name="seq", causal=False):
    """shard_map-wrapped ring attention over ``mesh``: takes/returns
    sequence-sharded (B, T, H, D) arrays."""
    from jax.sharding import PartitionSpec as P
    from veles_tpu.parallel.mesh import shard_map

    spec = P(None, axis_name, None, None)
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal)
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))


# -- Ulysses (all-to-all) sequence parallelism --------------------------------

def ulysses_attention(q, k, v, axis_name, causal=False, scale=None):
    """All-to-all sequence parallelism (the DeepSpeed-Ulysses pattern)
    inside shard_map: ``q/k/v`` are LOCAL sequence blocks
    (B, T_local, H, D). One ``all_to_all`` swaps the sequence sharding
    for HEAD sharding — each device then holds the FULL sequence for
    ``H / axis_size`` heads and runs ordinary fused attention locally —
    and the inverse all_to_all restores the sequence layout.

    Trade-off vs :func:`ring_attention`: four collectives per call
    (q/k/v in, output back) instead of ``2 * axis_size`` ppermute
    rounds (better for fat ICI all-to-all and moderate sequence
    lengths), but it requires
    ``heads % axis_size == 0`` and materializes the full sequence per
    device for its head slice (HBM scales with T, not T/n)."""
    n = lax.axis_size(axis_name)
    heads = q.shape[2]
    if heads % n:
        raise ValueError("ulysses needs heads (%d) divisible by the "
                         "%r axis size (%d)" % (heads, axis_name, n))

    def seq_to_heads(x):  # (B, T/n, H, D) -> (B, T, H/n, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    out = attention(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
                    causal=causal, scale=scale)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def make_ulysses_attention(mesh, axis_name="seq", causal=False):
    """shard_map-wrapped Ulysses attention over ``mesh``: takes/returns
    sequence-sharded (B, T, H, D) arrays (same contract as
    :func:`make_ring_attention` — the two are drop-in alternatives)."""
    from jax.sharding import PartitionSpec as P
    from veles_tpu.parallel.mesh import shard_map

    spec = P(None, axis_name, None, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal)
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
