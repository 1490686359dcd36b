"""Plain reference for the ``lfm2-8b-a1b`` configuration.

The layers of LFM2-8B-A1B (``model_type`` ``lfm2_moe``) as
``benchmark/configs/lfm2-8b-a1b.json`` states them, in straightforward
``jax.numpy``: float32 under ``jax.default_matmul_precision("highest")``,
the whole sequence at once, no cache, no slots, no batching, the
convolution as an explicit sum over three shifted copies, the K/V heads
repeated for their groups, the experts as a loop. None of the
mathematics is taken from ``veles_tpu``.

Sizes: hidden 2048, RMSNorm (``norm_eps`` 1e-5), no bias anywhere;
``layer_types`` says for each layer whether its operator is ``conv``
(a gated short convolution, ``conv_L_cache`` 3, ``conv_bias`` false) or
``full_attention`` (32 query heads over 8 K/V heads of 64, RoPE theta
1e6); the first ``num_dense_layers`` layers have a dense SwiGLU of
``intermediate_size`` 7168, the others 32 routed experts of
``moe_intermediate_size`` 1792, 4 a token (sigmoid scores,
``use_expert_bias``, ``norm_topk_prob``, ``routed_scaling_factor`` 1),
no shared expert; vocabulary 65,536, the head tied to the embedding.

- Block: ``x <- x + operator(RMSNorm(x))``, ``x <- x + ffn(RMSNorm(x))``;
  after the last block RMSNorm, then the head: the embedding table,
  transposed.
- ``conv`` operator, ``h`` the normed input: ``[B | C | u] = h.W_in``
  (2048 -> 3 x 2048); ``g = B * u``; ``z_t = w_0 * g_{t-2} + w_1 *
  g_{t-1} + w_2 * g_t`` (depthwise, causal, zeros before the sequence);
  ``out = (C * z).W_out``. (The served state of a slot after position
  ``t`` is ``g_{t-1}, g_t``; the reference keeps none.)
- ``full_attention`` operator: ``q = h.W_q`` as 32 heads of 64, ``k =
  h.W_k`` and ``v = h.W_v`` as 8 heads of 64; RMSNorm over the 64 of
  each head of q (``q_norm``) and of k (``k_norm``); RoPE on q and k;
  query head ``i`` attends K/V head ``i // 4`` (here: K and V repeated
  4 times), causal softmax in float32, scale 1/8; ``out = att.W_o``.
  RoPE rotates the pairs ``(2i, 2i+1)`` of the 64 by ``t.theta^(-2i/64)``,
  as complex numbers (the configuration's ``departures``: the
  published code rotates halves, a permutation of q and k alike).
- Dense ffn: ``W_down(silu(W_gate h) * W_up h)``, width 7168.
- Routed ffn: ``s = sigmoid(h.W_g)`` (32, float32); chosen = top-4 of
  ``s + b`` (``b`` the expert bias, in the choice only); ``w =
  s[chosen] / (sum s[chosen] + 1e-6) . 1``; ``y = sum_i w_i.E_i(h)``,
  every expert a SwiGLU of width 1792. Every expert is computed for
  every token and weighted by ``w`` or 0, a group of experts at a
  time, widened from the bfloat16 leaves a group at a time: no token
  can be dropped, and the reference fits beside the weights it checks.

The weights are the configuration's: bfloat16 values made here on the
device from the seed in one jitted call (``init_params``), under the
leaf names of ``veles_tpu.parallel.blocks`` (``Grouped``, ``ShortConv``,
``ops/moe``), with the architecture (a kind for each block) riding in
``params["arch"]``. The reference widens those same values to float32;
``operands`` instead rounds both operands of every matrix product
(weights and activations, attention and router included) to a lower
type first: ``"float8_e4m3fn"`` is the control the configuration names.

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

#: sequences are padded to a multiple of this so that a handful of
#: programs serve every length (the pad sits after the last position,
#: where a causal model cannot see it)
PAD = 128
#: experts widened to float32 at a time
GROUP = 4
#: the published ``layer_types`` under the names of the program's kinds
KINDS = {"conv": "conv", "full_attention": "gqa"}


def sizes(config):
    """The sizes the forward needs, hashable."""
    return tuple(config[key] for key in (
        "n_head", "num_key_value_heads", "norm_eps", "rope_theta",
        "num_experts_per_tok", "routed_scaling_factor"))


def init_params(seed, config):
    """``(params, embed_table)`` in bfloat16 on the default device:
    matrices N(0, 1/fan_in), the convolution's taps N(0, 1/3), norm
    gains 1 + N(0, 0.02), the router's selection bias N(0, 0.02) in
    float32, table N(0, 0.02). Every leaf is drawn on its own, so
    nothing twice its size ever stands. No ``head`` leaf: the head is
    the table."""
    from veles_tpu.parallel.blocks import Arch

    e, v = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = config["n_head"], config["num_key_value_heads"]
    head_dim = e // heads
    taps = config["conv_L_cache"]
    experts, width = config["num_experts"], \
        config["moe_intermediate_size"]
    dense = config["num_dense_layers"]
    kinds = tuple(KINDS[kind] for kind in config["layer_types"])
    assert len(kinds) == config["num_hidden_layers"], kinds
    # the architecture rides with the parameters (a static node); a
    # program that knows no kind a block refuses here, before any
    # weight is made
    arch = Arch(
        layers=kinds, eps=config["norm_eps"], kv_heads=kv_heads,
        conv_taps=taps, rope_theta=float(config["rope_theta"]),
        top_k=config["num_experts_per_tok"],
        route_scale=float(config["routed_scaling_factor"]),
        route_eps=1e-6,
        prefill_tokens=config["serving"].get("prefill_tokens", 0))
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
        for layer, kind in enumerate(kinds):
            blk = {"attn_norm": gain(e), "ffn_norm": gain(e)}
            if kind == "conv":
                blk.update(w_in=mat(e, 3 * e),
                           conv_w=normal((taps, e), 1.0 / math.sqrt(taps)),
                           w_out=mat(e, e))
            else:
                blk.update(wq=mat(e, heads * head_dim),
                           wk=mat(e, kv_heads * head_dim),
                           wv=mat(e, kv_heads * head_dim),
                           q_norm=gain(head_dim), k_norm=gain(head_dim),
                           wout=mat(heads * head_dim, e))
            if layer < dense:
                blk.update(swiglu(config["intermediate_size"]))
            else:
                blk.update(
                    router=mat(e, experts),
                    router_bias=normal((experts,), 0.02,
                                       dtype=jnp.float32),
                    experts=swiglu(width, (experts,)))
            blocks.append(blk)
        return {"blocks": blocks, "norm_w": gain(e)}, normal((v, e), 0.02)

    # the counter-mode generator the TPU has in hardware ("rbg"): the
    # default threefry takes minutes for 4.6 G numbers there
    seed = int(seed)
    params, table = make(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31))
    params["arch"] = arch
    return params, table


def _round(x, operands):
    x = x.astype(jnp.float32)
    if operands == "float32":
        return x
    return x.astype(jnp.dtype(operands)).astype(jnp.float32)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """``x`` (T, H, R) at positions 0..T-1, pairs as complex numbers."""
    t, r = x.shape[0], x.shape[-1]
    turn = jnp.exp(1j * jnp.arange(t)[:, None]
                   * theta ** (-jnp.arange(0, r, 2) / r))[:, None]
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    turned = (pairs[..., 0] + 1j * pairs[..., 1]) * turn
    return jnp.stack([turned.real, turned.imag], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dims", "operands"))
def _logits_at(blocks, norm_w, table, tokens, positions, dims, operands):
    """``(logits (len(positions), V), chosen)`` of the sequence
    ``tokens`` (T,) at ``positions``; ``chosen`` (expert layers, T,
    top_k) are the experts each token was routed to."""
    heads, kv_heads, eps, theta, top_k, scale = dims

    def mm(x, w):
        return jnp.dot(_round(x, operands), _round(w, operands))

    def swiglu(h, p):
        return mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
                  p["w_down"])

    t = tokens.shape[0]
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    causal = jnp.tril(jnp.ones((t, t), bool))
    chosen_all = []
    with jax.default_matmul_precision("highest"):
        for blk in blocks:
            h = _rms(x, blk["attn_norm"], eps)
            if "w_in" in blk:
                b, c, u = jnp.split(mm(h, blk["w_in"]), 3, axis=-1)
                gated = b * u
                taps = blk["conv_w"].astype(jnp.float32)
                last = taps.shape[0] - 1
                z = jnp.zeros_like(gated)
                for j in range(last + 1):
                    # tap j sees the gated input (last - j) positions back
                    back = last - j
                    shifted = jnp.concatenate(
                        [jnp.zeros_like(gated[:back]), gated[:t - back]])
                    z = z + taps[j] * shifted
                x = x + mm(c * z, blk["w_out"])
            else:
                q = mm(h, blk["wq"]).reshape(t, heads, -1)
                k = mm(h, blk["wk"]).reshape(t, kv_heads, -1)
                v = mm(h, blk["wv"]).reshape(t, kv_heads, -1)
                q = _rope(_rms(q, blk["q_norm"], eps), theta)
                k = _rope(_rms(k, blk["k_norm"], eps), theta)
                # query head i attends K/V head i // (heads // kv_heads)
                k = jnp.repeat(k, heads // kv_heads, axis=1)
                v = jnp.repeat(v, heads // kv_heads, axis=1)
                s = jnp.einsum("qhd,khd->hqk", _round(q, operands),
                               _round(k, operands)) \
                    / math.sqrt(q.shape[-1])
                p_att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
                att = jnp.einsum("hqk,khd->qhd", _round(p_att, operands),
                                 _round(v, operands))
                x = x + mm(att.reshape(t, -1), blk["wout"])
            h = _rms(x, blk["ffn_norm"], eps)
            if "router" not in blk:
                x = x + swiglu(h, blk)
                continue
            scores = jax.nn.sigmoid(mm(h, blk["router"]))
            _, chosen = lax.top_k(scores + blk["router_bias"], top_k)
            picked = jnp.take_along_axis(scores, chosen, -1)
            weight = jnp.zeros_like(scores).at[
                jnp.arange(t)[:, None], chosen].set(
                picked / (jnp.sum(picked, -1, keepdims=True) + 1e-6)
                * scale)
            chosen_all.append(chosen)

            def group(y, part, h=h):
                # GROUP experts over every token, each weighted by the
                # token's share of it (0 where it was not chosen)
                share, experts = part
                inner = jax.nn.silu(jnp.einsum(
                    "te,gef->gtf", _round(h, operands),
                    _round(experts["w_gate"], operands))) \
                    * jnp.einsum("te,gef->gtf", _round(h, operands),
                                 _round(experts["w_up"], operands))
                out = jnp.einsum("gtf,gfe->gte", _round(inner, operands),
                                 _round(experts["w_down"], operands))
                return y + jnp.einsum("gte,tg->te", out, share), None

            n_groups = weight.shape[1] // GROUP
            routed, _ = lax.scan(
                group, jnp.zeros_like(x),
                (weight.reshape(t, n_groups, GROUP).swapaxes(0, 1),
                 jax.tree.map(
                     lambda w: w.reshape((n_groups, GROUP) + w.shape[1:]),
                     blk["experts"])))
            x = x + routed
        x = _rms(jnp.take(x, positions, axis=0), norm_w, eps)
        # the head is the embedding table, transposed
        logits = jnp.einsum("pe,ve->pv", _round(x, operands),
                            _round(table, operands))
        return logits, jnp.stack(chosen_all)


def stack_blocks(params):
    """The blocks as they are: they differ in kind, and a second copy
    of 8.6 GiB would not fit beside the first."""
    return params["blocks"]


def logits_after(config, params, table, prompt, served,
                 operands="float32", stacked=None, with_chosen=False):
    """Reference logits (len(served), V): row i is the distribution
    from which answered token i is drawn, i.e. at the last prompt
    position and then after each answered token but the last."""
    tokens = list(prompt) + list(served[:-1])
    first = len(prompt) - 1
    padded = -(-len(tokens) // PAD) * PAD
    n_out = config["serving"]["n_tokens"]
    ids = jnp.asarray(tokens + [0] * (padded - len(tokens)), jnp.int32)
    positions = jnp.asarray(
        [first + i for i in range(len(served))]
        + [first] * (n_out - len(served)), jnp.int32)
    out, chosen = _logits_at(params["blocks"], params["norm_w"], table,
                             ids, positions, sizes(config), operands)
    if with_chosen:
        return out[:len(served)], chosen[:, :len(tokens)]
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
        logits_after(config, params, table, prompt, served),
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

    want = logits_after(config, params, table, prompt, served)
    low = logits_after(config, params, table, prompt, served,
                       operands=operands)
    gaps = numpy.asarray(_gaps(want, jnp.argmax(low, -1)))
    print("reference: control gap mean %.4f widest token %.4f over %d "
          "tokens" % (gaps.mean(), gaps.max(), len(gaps)),
          file=sys.stderr)
    return gaps.mean(keepdims=True)


def route_flips(config, params, table, prompt, served,
                operands="bfloat16"):
    """How often rounding to ``operands`` changes a token's set of
    experts: ``(changed, of)`` over the sequence's (expert layer,
    token) pairs, each side computed whole at its own precision."""
    import numpy

    sets = [numpy.sort(numpy.asarray(logits_after(
        config, params, table, prompt, served, operands=kind,
        with_chosen=True)[1]), -1) for kind in ("float32", operands)]
    changed = (sets[0] != sets[1]).any(-1)
    return int(changed.sum()), int(changed.size)
