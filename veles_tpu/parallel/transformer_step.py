"""Sequence-parallel transformer training: one fused step under a
``data`` x ``seq`` mesh.

The long-context training integration: activations are sharded over BOTH
the batch (``data``) and the sequence (``seq``) axes; attention runs
sequence-parallel via either SP strategy — Ulysses all-to-all (default;
plain differentiable composition) or ring attention (``lax.scan``-based
online softmax, reverse-differentiable, HBM per device scales with T/n);
every other sublayer (layer norm, MLP, residuals, the per-token head) is
token-local, so only the attention pays collectives. Gradients ``psum``
over both axes.

No reference counterpart (VELES predates attention; SURVEY §5
"Long-context: absent") — this is the additive tier the build brief makes
first-class. The causal-LM toy model here (pre-LN blocks, GELU MLP,
per-token softmax head) is the standard shape scaling recipes assume.
"""

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.ops.quant import matmul_any
from veles_tpu.parallel.mesh import shard_map
from veles_tpu.ops.attention import (attention, ring_attention,
                                     ulysses_attention)


def init_transformer_params(rng, n_blocks, embed, heads, vocab,
                            mlp_ratio=4):
    """Plain float32 pytree; ``rng`` is a numpy RandomState."""
    def mat(a, b):
        return jnp.asarray(rng.randn(a, b).astype("float32")
                           / math.sqrt(a))

    hidden = embed * mlp_ratio
    blocks = []
    for _ in range(n_blocks):
        blocks.append({
            "ln1_w": jnp.ones(embed), "ln1_b": jnp.zeros(embed),
            "wqkv": mat(embed, 3 * embed), "bqkv": jnp.zeros(3 * embed),
            "wout": mat(embed, embed), "bout": jnp.zeros(embed),
            "ln2_w": jnp.ones(embed), "ln2_b": jnp.zeros(embed),
            "w1": mat(embed, hidden), "b1": jnp.zeros(hidden),
            "w2": mat(hidden, embed), "b2": jnp.zeros(embed),
        })
    return {"blocks": blocks,
            "lnf_w": jnp.ones(embed), "lnf_b": jnp.zeros(embed),
            "head": mat(embed, vocab)}


def _ln(x, w, b, eps=1e-5):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


# The sublayer helpers are shared with the KV-cache decode path
# (parallel/decode.py) — ONE copy of the block math keeps the cached
# and full-recompute forwards numerically equivalent by construction.
# Each writes its ``jax.named_scope`` (``attn.qkv``, ``mlp``, ``head``:
# HLO metadata, nothing at run time), which the scope table
# (observe/xla_stats.scope_table) carries to a traced op.

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


def _forward(params, x, heads, seq_ax, sp_strategy):
    batch, t, embed = x.shape
    for blk in params["blocks"]:
        q, k, v = _block_qkv(blk, x, heads)
        if seq_ax > 1 and sp_strategy == "ring":
            att = ring_attention(q, k, v, "seq", causal=True)
        elif seq_ax > 1:
            att = ulysses_attention(q, k, v, "seq", causal=True)
        else:
            att = attention(q, k, v, causal=True)
        x = x + matmul_any(att.reshape(batch, t, embed),
                           blk["wout"]) + blk["bout"]
        x = _mlp(blk, x)
    return _head(params, x)


def build_transformer_train_step(heads, mesh=None, learning_rate=0.1,
                                 sp_strategy="ulysses"):
    """Compile ``step(params, x, labels) -> (params, (loss, n_err))``:
    per-token causal-LM softmax xent, SGD update. With a mesh, ``x`` and
    ``labels`` shard over (data, seq) and gradients psum over both;
    ``sp_strategy`` picks "ulysses" (all-to-all) or "ring" attention."""
    if sp_strategy not in ("ulysses", "ring"):
        raise ValueError("sp_strategy must be 'ulysses' or 'ring', got %r"
                         % (sp_strategy,))
    data_ax = mesh.shape.get("data", 1) if mesh is not None else 1
    seq_ax = mesh.shape.get("seq", 1) if mesh is not None else 1

    def local_step(params, x, labels):
        # static: shard shapes are known at trace time — no collective
        n_tokens = jnp.float32(
            x.shape[0] * x.shape[1] * data_ax * seq_ax)

        def loss_fn(params):
            logits = _forward(params, x, heads, seq_ax, sp_strategy)
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                logp, labels[..., None], axis=-1)[..., 0]
            n_err = jnp.sum(jnp.argmax(logits, -1) != labels)
            return -jnp.sum(picked) / n_tokens, n_err

        (loss, n_err), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        for axis, size in (("data", data_ax), ("seq", seq_ax)):
            if size > 1:
                grads = jax.lax.psum(grads, axis)
                loss = jax.lax.psum(loss, axis)
                n_err = jax.lax.psum(n_err, axis)
        new = jax.tree.map(lambda p, g: p - learning_rate * g, params,
                           grads)
        return new, (loss, n_err)

    if mesh is None or (data_ax == 1 and seq_ax == 1):
        return jax.jit(local_step)
    xspec = P("data", "seq", None)
    in_specs = (P(), xspec, P("data", "seq"))
    out_specs = (P(), (P(), P()))
    return jax.jit(shard_map(local_step, mesh=mesh,
                             in_specs=in_specs, out_specs=out_specs))


def shard_tokens(arrays, mesh):
    """Place (x, labels) with (data, seq) sharding."""
    specs = (P("data", "seq", None), P("data", "seq"))
    return [jax.device_put(a, NamedSharding(mesh, s))
            for a, s in zip(arrays, specs)]
