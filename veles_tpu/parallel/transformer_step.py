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

from veles_tpu.ops.attention import ring_attention, ulysses_attention
from veles_tpu.parallel import blocks
from veles_tpu.parallel.blocks import (  # noqa: F401  (their old home)
    _block_qkv, _head, _ln, _mlp)
from veles_tpu.parallel.mesh import shard_map


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


# The sublayers live in ``parallel/blocks.py`` (the model seam): ONE
# definition each, shared with the KV-cache decode path
# (parallel/decode.py), keeps the cached and full-recompute forwards
# numerically equivalent by construction. GPT-2's helpers keep their
# names here for the callers that import them from this module.

def _forward(params, x, heads, seq_ax, sp_strategy, embed_table=None):
    """The plain full forward of whatever blocks ``params`` declare
    (``blocks.arch_of``, a kind a block). Sequence parallelism is
    GPT-2's alone (it has no position encoding to shard).
    ``embed_table`` is the head of a model that ties the two."""
    batch, t, embed = x.shape
    arch = blocks.arch_of(params)
    if seq_ax > 1:
        blocks.require_gpt2(params, "sequence-parallel training")
    positions = jnp.broadcast_to(jnp.arange(t), (batch, t))
    for blk, kind in zip(params["blocks"], blocks.block_kinds(
            arch, len(params["blocks"]))):
        if seq_ax > 1:
            q, rows = kind.project(arch, blk, x, heads, positions)
            spread = ring_attention if sp_strategy == "ring" \
                else ulysses_attention
            att = spread(q, rows["k"], rows["v"], "seq", causal=True)
            x = kind.out(blk, x, att.reshape(batch, t, embed))
            x, _ = blocks.ffn(arch, blk, x)
        else:
            x, _ = blocks.block_forward(arch, blk, x, heads, positions,
                                        kind=kind)
    return blocks.head(arch, params, x, embed_table)


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
        # training any block but GPT-2's is not built yet
        blocks.require_gpt2(params, "the transformer train step")
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
