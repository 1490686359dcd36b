"""Plain reference for the ``command-a-plus-05-2026`` configuration.

The layers of Command A+ (``model_type`` ``cohere2_moe``) as
``benchmark/configs/command-a-plus-05-2026.json`` states them, in
straightforward ``jax.numpy``: float32 under
``jax.default_matmul_precision("highest")``, the whole sequence at
once, no cache, no ring, no slots, no batching. The window is a mask
on the score, and attention runs a K/V group (its 16 query heads) and
a block of queries at a time so that 8,704 positions fit; the experts
are a loop over the held ones and the shared experts a loop of four,
averaged. None of the mathematics is taken from ``veles_tpu``.

Sizes: hidden 4096, LayerNorm (``layer_norm_eps`` 1e-5) with a gain and
no bias, no bias anywhere; 128 query heads over 8 K/V heads of 128;
``layer_types`` says for each layer whether it attends a window
(``sliding_attention``: the last ``sliding_window`` positions, the
query's own included, RoPE on q and k over interleaved pairs, theta
50,000) or the whole sequence (``full_attention``: no position
encoding); 128 routed experts of width 4096, 8 a token, of which this
chip holds the first ``num_experts``; four shared experts of width 4096,
averaged; the vocabulary slice of ``vocab_size`` rows, the head tied to
the embedding.

- Block (parallel): ``h = LN(x)``; ``x <- x + A(h) + R(h) + S(h)``;
  after the last block LN, then the head: the embedding table,
  transposed.
- ``A``: ``q = h.W_q`` as 128 heads, ``k = h.W_k`` and ``v = h.W_v`` as
  8; query head ``i`` attends K/V head ``i // 16``; causal softmax in
  float32, scale 1/sqrt(128); ``out = concat(y).W_o``.
- ``R``: ``s = sigmoid(h.W_r)`` over all 128 experts (float32); the
  chosen are the top 8 of ``s``; ``w = s[chosen] / sum s[chosen]``;
  ``R = sum_{e chosen and held} w_e E_e(h)``, ``E(h) = W_down(silu(W_gate
  h) * W_up h)``. Every held expert is computed for every token and
  weighted by ``w_e`` or 0, one at a time, widened from its bfloat16
  leaves as it is used.
- ``S``: ``(1/4) sum_s E^shared_s(h)``, the four read from the served
  leaves' four blocks of 4096 columns (rows of ``w_down``).

The weights are the configuration's: bfloat16 values made here on the
device from the seed in one jitted call (``init_params``), under the
leaf names of ``veles_tpu.parallel.blocks`` (``Windowed``/``Global``)
and ``ops/moe`` (``router``, ``experts``, ``shared``), with the
architecture riding in ``params["arch"]``. The reference widens those
same values to float32; ``operands`` instead rounds both operands of
every matrix product (weights and activations, attention and router
included) to a lower type first: ``"float8_e4m3fn"`` is the control the
configuration names.

``served_gaps`` is the comparison: for a prompt and the tokens the
server answered with (greedy), the reference's logits at each answered
position, how far the answered token's logit lies below the
reference's best there, and of that the **mean over the request's
answered tokens** (the configuration's ``limits_note`` has the readings
on the chip and why the mean). Each call also writes the widest
token's gap beside the mean to standard error, for the calibration.
"""

import functools
import math
import sys

import jax
import jax.numpy as jnp
from jax import lax

#: sequences are padded to the next power of two from 128 up to this,
#: and to a multiple of it past it, so that a handful of programs serve
#: every length (the pad sits after the last position, where a causal
#: model cannot see it)
PAD = 2048
#: queries a block of the attention takes at once
QUERY_BLOCK = 1024
#: the published ``layer_types`` under the names of the program's kinds
KINDS = {"sliding_attention": "swa", "full_attention": "nope"}


def sizes(config):
    """The sizes the forward needs, hashable."""
    return (config["n_head"], config["num_key_value_heads"],
            config["layer_norm_eps"], float(config["rope_theta"]),
            config["num_experts_per_tok"], config["sliding_window"],
            config["num_shared_experts"],
            tuple(KINDS[kind] for kind in config["layer_types"]))


def arch(config, held=None):
    """The program's architecture for ``config``: ``held`` (first,
    count) of the experts, the configuration's by default."""
    from veles_tpu.parallel.blocks import Arch

    return Arch(
        layers=tuple(KINDS[kind] for kind in config["layer_types"]),
        eps=config["layer_norm_eps"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        top_k=config["num_experts_per_tok"],
        held=held or (0, config["num_experts"]),
        prefill_tokens=config["serving"].get("prefill_tokens", 0),
        admit_tokens=config["serving"].get("admit_tokens", 0),
        window=config["sliding_window"], parallel=True, norm="layer",
        shared_scale=1.0 / config["num_shared_experts"])


def init_params(seed, config):
    """``(params, embed_table)`` in bfloat16 on the default device:
    matrices N(0, 1/fan_in), norm gains 1 + N(0, 0.02), table N(0,
    0.02). Every leaf is drawn on its own, so nothing twice its size
    ever stands. No ``head`` leaf: the head is the table."""
    e, v = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = config["n_head"], config["num_key_value_heads"]
    head_dim, width = config["head_dim"], config["intermediate_size"]
    held, routed = config["num_experts"], config["routed_experts"]
    shared = config["num_shared_experts"]
    # the architecture rides with the parameters (a static node); a
    # program that knows no kind a block refuses here, before any
    # weight is made
    model = arch(config)
    bf = jnp.bfloat16

    @jax.jit
    def make(key):
        count = iter(range(1 << 20))

        def normal(shape, scale, mean=0.0, dtype=bf):
            k = jax.random.fold_in(key, next(count))
            return (mean + scale * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dtype)

        def mat(a, b, lead=()):
            return normal(lead + (a, b), 1.0 / math.sqrt(a))

        def gain(n):
            return normal((n,), 0.02, 1.0)

        def swiglu(hidden, lead=()):
            return {"w_gate": mat(e, hidden, lead),
                    "w_up": mat(e, hidden, lead),
                    "w_down": mat(hidden, e, lead)}

        blocks = []
        for _ in model.layers:
            blocks.append({
                "attn_norm": gain(e),
                "wq": mat(e, heads * head_dim),
                "wk": mat(e, kv_heads * head_dim),
                "wv": mat(e, kv_heads * head_dim),
                "wout": mat(heads * head_dim, e),
                "router": mat(e, routed),
                "experts": swiglu(width, (held,)),
                # the shared experts side by side: one SwiGLU of
                # shared x width whose output is their sum
                "shared": swiglu(shared * width)})
        return {"blocks": blocks, "norm_w": gain(e)}, normal((v, e), 0.02)

    # the counter-mode generator the TPU has in hardware ("rbg"): the
    # default threefry takes minutes for 4.7 G numbers there
    seed = int(seed)
    params, table = make(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31))
    params["arch"] = model
    return params, table


def _round(x, operands):
    x = x.astype(jnp.float32)
    if operands == "float32":
        return x
    return x.astype(jnp.dtype(operands)).astype(jnp.float32)


def _ln(x, w, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(jnp.square(centred), -1,
                                       keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """``x`` (T, H, R) at positions 0..T-1, pairs as complex numbers."""
    t, r = x.shape[0], x.shape[-1]
    turn = jnp.exp(1j * jnp.arange(t)[:, None]
                   * theta ** (-jnp.arange(0, r, 2) / r))[:, None]
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    turned = (pairs[..., 0] + 1j * pairs[..., 1]) * turn
    return jnp.stack([turned.real, turned.imag], -1).reshape(x.shape)


def attend(q, k, v, window, operands="float32"):
    """Causal attention of ``q`` (T, H, d) over ``k``, ``v`` (T, G, d),
    query head ``i`` on K/V head ``i // (H / G)``; with ``window`` the
    query at ``t`` sees ``j`` in ``(t - window, t]``. A K/V group and
    ``QUERY_BLOCK`` queries at a time: (T, H, d)."""
    t, heads, d = q.shape
    groups = k.shape[1]
    block = min(QUERY_BLOCK, t)
    keys = jnp.arange(t)

    def one(args):
        qg, kg, vg, at = args                   # (B, R, d), (T, d), at
        rows = at + jnp.arange(block)
        s = jnp.einsum("qrd,kd->rqk", _round(qg, operands),
                       _round(kg, operands)) / math.sqrt(d)
        seen = keys[None, :] <= rows[:, None]
        if window:
            seen &= keys[None, :] > rows[:, None] - window
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("rqk,kd->qrd", _round(p, operands),
                          _round(vg, operands))

    qs = q.reshape(t // block, block, groups, heads // groups, d)
    out = lax.map(lambda g: lax.map(
        lambda b: one((qs[b, :, g], k[:, g], v[:, g], b * block)),
        jnp.arange(t // block)), jnp.arange(groups))
    # (G, T/B, B, R, d) -> (T, H, d)
    return jnp.moveaxis(out, 0, 2).reshape(t, heads, d)


@functools.partial(jax.jit, static_argnames=("dims", "operands"))
def _logits_at(blocks, norm_w, table, tokens, positions, dims, operands):
    """``logits (len(positions), V)`` of the sequence ``tokens`` (T,)
    at ``positions``, the blocks' held experts those of their leaves
    (their first id is the leaves' own: ``blocks[i]["first"]``)."""
    heads, kv_heads, eps, theta, top_k, window, n_shared, kinds = dims

    def mm(x, w):
        return jnp.dot(_round(x, operands), _round(w, operands))

    def swiglu(h, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)

    t = tokens.shape[0]
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for blk, kind in zip(blocks, kinds):
            h = _ln(x, blk["attn_norm"], eps)
            q = mm(h, blk["wq"]).reshape(t, heads, -1)
            k = mm(h, blk["wk"]).reshape(t, kv_heads, -1)
            v = mm(h, blk["wv"]).reshape(t, kv_heads, -1)
            if kind == "swa":
                q, k = _rope(q, theta), _rope(k, theta)
            att = attend(q, k, v, window if kind == "swa" else 0,
                         operands)
            out = mm(att.reshape(t, -1), blk["wout"])
            # the router over every expert; the held ones computed
            scores = jax.nn.sigmoid(mm(h, blk["router"]))
            _, chosen = lax.top_k(scores, top_k)
            picked = jnp.take_along_axis(scores, chosen, -1)
            weight = jnp.zeros_like(scores).at[
                jnp.arange(t)[:, None], chosen].set(
                picked / jnp.sum(picked, -1, keepdims=True))
            experts = blk["experts"]
            held = lax.dynamic_slice_in_dim(
                weight, blk["first"], experts["w_gate"].shape[0], 1)

            def expert(y, part, h=h):
                share, w_gate, w_up, w_down = part
                return y + share[:, None] * swiglu(h, w_gate, w_up,
                                                   w_down), None

            routed, _ = lax.scan(expert, jnp.zeros_like(x), (
                held.T, experts["w_gate"], experts["w_up"],
                experts["w_down"]))
            p = blk["shared"]
            width = p["w_gate"].shape[1] // n_shared
            cols = [slice(s * width, (s + 1) * width)
                    for s in range(n_shared)]
            shared = sum(swiglu(h, p["w_gate"][:, c], p["w_up"][:, c],
                                p["w_down"][c]) for c in cols) / n_shared
            x = x + out + routed + shared
        x = _ln(jnp.take(x, positions, axis=0), norm_w, eps)
        # the head is the embedding table, transposed
        return jnp.einsum("pe,ve->pv", _round(x, operands),
                          _round(table, operands))


def stack_blocks(params):
    """The blocks as they are, each with the id of its first held
    expert: a second copy of 9.5 GB would not fit beside the first."""
    held = params["arch"].held
    return [dict(blk, first=jnp.int32(held[0]))
            for blk in params["blocks"]]


def logits_after(config, params, table, prompt, served,
                 operands="float32", stacked=None):
    """Reference logits (len(served), V): row i is the distribution
    from which answered token i is drawn, i.e. at the last prompt
    position and then after each answered token but the last."""
    tokens = list(prompt) + list(served[:-1])
    first = len(prompt) - 1
    padded = 128
    while padded < min(len(tokens), PAD):
        padded *= 2
    padded = max(padded, -(-len(tokens) // PAD) * PAD)
    n_out = config["serving"]["n_tokens"]
    ids = jnp.asarray(tokens + [0] * (padded - len(tokens)), jnp.int32)
    positions = jnp.asarray(
        [first + i for i in range(len(served))]
        + [first] * (n_out - len(served)), jnp.int32)
    out = _logits_at(stacked if stacked is not None
                     else stack_blocks(params), params["norm_w"], table,
                     ids, positions, sizes(config), operands)
    return out[:len(served)]


def _gaps(want, tokens):
    """How far each of ``tokens``' logits lies below the best of its
    row of ``want``."""
    picked = jnp.take_along_axis(want, tokens[:, None], 1)[:, 0]
    return jnp.max(want, -1) - picked


def served_gaps(config, params, table, prompt, served, stacked=None):
    """How far an answered token's reference logit lies below the
    reference's best at its position (0 where the reference would
    have answered the same), the mean over the request's answered
    tokens: a float32 vector of one number on the host."""
    import numpy

    gaps = numpy.asarray(_gaps(
        logits_after(config, params, table, prompt, served,
                     stacked=stacked),
        jnp.asarray(served, jnp.int32)))
    print("reference: served gap mean %.4f widest token %.4f over %d "
          "tokens" % (gaps.mean(), gaps.max(), len(gaps)),
          file=sys.stderr)
    return gaps.mean(keepdims=True)


def control_gaps(config, params, table, prompt, served, operands,
                 stacked=None):
    """The control: at each position of the same prompt and answered
    tokens, the gap (under the reference) of the token that the lower
    precision ``operands`` puts first; the mean, as ``served_gaps``."""
    import numpy

    want = logits_after(config, params, table, prompt, served,
                        stacked=stacked)
    low = logits_after(config, params, table, prompt, served,
                       operands=operands, stacked=stacked)
    gaps = numpy.asarray(_gaps(want, jnp.argmax(low, -1)))
    print("reference: control gap mean %.4f widest token %.4f over %d "
          "tokens" % (gaps.mean(), gaps.max(), len(gaps)),
          file=sys.stderr)
    return gaps.mean(keepdims=True)
