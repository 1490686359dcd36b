"""Plain reference for the ``solar-open2-250b`` configuration.

The layers of Solar Open 2 (``model_type`` ``solar_open2``) as
``benchmark/configs/solar-open2-250b.json`` states them, in
straightforward ``jax.numpy``: float32 under
``jax.default_matmul_precision("highest")``, the whole sequence at
once, no cache, no slots, no batching. The delta rule runs as its
**recurrence, a position at a time** (``lax.scan`` over the sequence):
no chunk, no WY form, no triangular system. Attention runs a K/V group
(its 8 query heads) and a block of queries at a time so that 8,960
positions fit; the experts are a loop over the held ones. None of the
mathematics is taken from ``veles_tpu``.

Sizes: hidden 4096, RMSNorm (``rms_norm_eps`` 1e-5), no bias anywhere;
a period of four layers, ``gqa_layers`` softmax attention and the rest
gated delta-rule layers; every layer's feed-forward the routed experts
and the shared expert.

- Block (sequential): ``x <- x + Mix(RMS(x))``; ``x <- x + R(RMS(x)) +
  E(RMS(x))``; after the last block RMS, then the head (untied).
- GQA ``Mix``: ``q = h.W_q`` as 64 heads of 128, ``k = h.W_k`` and ``v =
  h.W_v`` as 8; query head ``i`` attends K/V head ``i // 8``; causal
  softmax in float32, scale 1/sqrt(128), no position encoding; ``y ⊙
  sigmoid(h.W_gate)``, then ``W_o``.
- KDA ``Mix`` (64 heads of 128; q, k, v alike): ``[q|k|v] = SiLU(conv4(h
  . W_qkv))``, each of the 24,576 channels its own causal 4-tap
  convolution, zeros before the sequence; ``q <- q / |q| / sqrt(128)``,
  ``k <- k / |k|`` (``+ 1e-6`` under the root); ``g = -exp(A_log) ·
  softplus((h.W_fa).W_fb + dt_bias)`` a channel, ``a = exp(g)``; ``b = 2
  sigmoid(h.W_beta)`` a head (``kda_allow_neg_eigval``: without it, ``b
  = sigmoid``); then for ``t = 1 .. T``, ``S`` (128 x 128) float32 from
  zero:

      S <- a_t ⊙ S              (each key channel's row decays)
      S <- S + b_t k_t (v_t - Sᵀ k_t)ᵀ
      o_t = Sᵀ q_t

  ``y = RMS_head(o) ⊙ sigmoid((h.W_ga).W_gb)``, then ``W_o``.
- ``R``: ``s = sigmoid(h.W_r)`` over all 320 experts (float32); the
  chosen are the top 8 of ``s + bias``; ``w = s[chosen] / sum
  s[chosen]``; ``R = sum_{e chosen and held} w_e E_e(h)``, ``E(h) =
  W_down(silu(W_gate h) * W_up h)``. Every held expert is computed for
  every token and weighted by ``w_e`` or 0, one at a time.
- ``E``: the shared expert, unscaled.

The weights are the configuration's: bfloat16 values (``A_log``,
``dt_bias`` and the router's bias float32) made here on the device from
the seed in one jitted call (``init_params``), under the leaf names of
``veles_tpu.parallel.blocks`` (``Global``, ``DeltaRule``) and
``ops/moe`` (``router``, ``router_bias``, ``experts``, ``shared``), with
the architecture riding in ``params["arch"]``. The reference widens
those same values to float32; ``operands`` instead rounds both operands
of every matrix product (weights and activations, attention, the delta
rule's products and the router included) to a lower type first:
``"float8_e4m3fn"`` is the control the configuration names.

``served_gaps`` is the comparison: for a prompt and the tokens the
server answered with (greedy), the reference's logits at each answered
position, how far the answered token's logit lies below the reference's
best there, and of that the **mean over the request's answered
tokens** (the configuration's ``limits_note`` has the readings on the
chip and why the mean). Each call also writes the widest token's gap
beside the mean to standard error, for the calibration.
"""

import functools
import math
import sys

import jax
import jax.numpy as jnp
from jax import lax

#: queries a block of the attention takes at once
QUERY_BLOCK = 1024


def layer_kinds(config):
    """The program's kind of each layer: ``"nope"`` at ``gqa_layers``,
    ``"kda"`` elsewhere."""
    return tuple("nope" if i in config["gqa_layers"] else "kda"
                 for i in range(config["num_hidden_layers"]))


def sizes(config):
    """The sizes the forward needs, hashable."""
    return (config["n_head"], config["num_key_value_heads"],
            config["rms_norm_eps"], config["num_experts_per_tok"],
            config["linear_attn_config"]["short_conv_kernel_size"],
            config["linear_attn_config"]["head_dim"],
            config["kda_allow_neg_eigval"], layer_kinds(config))


def arch(config, held=None):
    """The program's architecture for ``config``: ``held`` (first,
    count) of the experts, the configuration's by default."""
    from veles_tpu.parallel.blocks import Arch

    linear = config["linear_attn_config"]
    if linear["num_heads"] != config["n_head"] \
            or linear["head_dim"] != config["head_dim"] \
            or linear["num_kv_heads"] not in (None, linear["num_heads"]):
        raise ValueError(
            "the delta-rule layers are served with the attention's "
            "heads (q, k and v alike); this configuration's "
            "linear_attn_config differs: %r" % (linear,))
    if not config["kda_allow_neg_eigval"]:
        raise ValueError(
            "the program's delta rule writes with 2 sigmoid (b in (0, 2)); "
            "this configuration sets kda_allow_neg_eigval false")
    return Arch(
        layers=layer_kinds(config), eps=config["rms_norm_eps"],
        kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        top_k=config["num_experts_per_tok"],
        route_scale=float(config["routed_scaling_factor"]),
        held=held or (0, config["n_routed_experts"]),
        prefill_tokens=config["serving"].get("prefill_tokens", 0),
        admit_tokens=config["serving"].get("admit_tokens", 0),
        prompt_bucket=config["serving"].get("prompt_bucket", 0),
        conv_taps=linear["short_conv_kernel_size"])


def init_params(seed, config):
    """``(params, embed_table)`` on the default device, every leaf
    drawn on its own: matrices N(0, 1/fan_in), norm gains 1 + N(0,
    0.02), table N(0, 0.02), in bfloat16; ``A_log = log U(1, 16)`` a
    head and ``dt_bias`` the inverse softplus of ``exp U(log 1e-3, log
    0.1)`` a channel, float32; the router's selection bias N(0, 0.02),
    float32."""
    e, v = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = config["n_head"], config["num_key_value_heads"]
    d, width = config["head_dim"], config["moe_intermediate_size"]
    held, routed = config["n_routed_experts"], config["routed_experts"]
    shared = config["n_shared_experts"]
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    # the architecture rides with the parameters (a static node); a
    # program that knows no kind a block refuses here, before any
    # weight is made
    model = arch(config)
    bf = jnp.bfloat16
    f32 = jnp.float32

    @jax.jit
    def make(key):
        count = iter(range(1 << 20))

        def draw():
            return jax.random.fold_in(key, next(count))

        def normal(shape, scale, mean=0.0, dtype=bf):
            return (mean + scale * jax.random.normal(draw(), shape, f32)
                    ).astype(dtype)

        def uniform(shape, low, high):
            return jax.random.uniform(draw(), shape, f32, low, high)

        def mat(a, b, lead=()):
            return normal(lead + (a, b), 1.0 / math.sqrt(a))

        def gain(n):
            return normal((n,), 0.02, 1.0)

        def swiglu(hidden, lead=()):
            return {"w_gate": mat(e, hidden, lead),
                    "w_up": mat(e, hidden, lead),
                    "w_down": mat(hidden, e, lead)}

        blocks = []
        for kind in model.layers:
            if kind == "nope":
                blk = {"attn_norm": gain(e),
                       "wq": mat(e, heads * d),
                       "wk": mat(e, kv_heads * d),
                       "wv": mat(e, kv_heads * d),
                       "wgate": mat(e, heads * d),
                       "wout": mat(heads * d, e)}
            else:
                rate = jnp.exp(uniform((heads * d,), math.log(1e-3),
                                       math.log(0.1)))
                blk = {"attn_norm": gain(e),
                       "w_qkv": mat(e, 3 * heads * d),
                       # a depthwise tap sums 4 inputs
                       "conv_w": normal((taps, 3 * heads * d),
                                        1.0 / math.sqrt(taps)),
                       "w_fa": mat(e, d), "w_fb": mat(d, heads * d),
                       "dt_bias": rate + jnp.log(-jnp.expm1(-rate)),
                       "A_log": jnp.log(uniform((heads,), 1.0, 16.0)),
                       "w_beta": mat(e, heads),
                       "w_ga": mat(e, d), "w_gb": mat(d, heads * d),
                       "o_norm": gain(d),
                       "wout": mat(heads * d, e)}
            blk.update({
                "ffn_norm": gain(e),
                "router": mat(e, routed),
                "router_bias": normal((routed,), 0.02, dtype=f32),
                "experts": swiglu(width, (held,)),
                "shared": swiglu(shared * width)})
            blocks.append(blk)
        return ({"blocks": blocks, "norm_w": gain(e),
                 "head": mat(e, v)}, normal((v, e), 0.02))

    # the counter-mode generator the TPU has in hardware ("rbg"): the
    # default threefry takes minutes for billions of numbers there
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


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def attend(q, k, v, operands="float32"):
    """Causal attention of ``q`` (T, H, d) over ``k``, ``v`` (T, G, d),
    query head ``i`` on K/V head ``i // (H / G)``: a K/V group and
    ``QUERY_BLOCK`` queries at a time, (T, H, d)."""
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
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("rqk,kd->qrd", _round(p, operands),
                          _round(vg, operands))

    qs = q.reshape(t // block, block, groups, heads // groups, d)
    out = lax.map(lambda g: lax.map(
        lambda b: one((qs[b, :, g], k[:, g], v[:, g], b * block)),
        jnp.arange(t // block)), jnp.arange(groups))
    # (G, T/B, B, R, d) -> (T, H, d)
    return jnp.moveaxis(out, 0, 2).reshape(t, heads, d)


def delta_rule(q, k, v, a, b, operands="float32"):
    """The recurrence, a position at a time: ``q``, ``k``, ``v``, ``a``
    (T, H, d), ``b`` (T, H) -> ``o`` (T, H, d); ``S`` (H, d_k, d_v)
    float32 from zero. The products ``Sᵀ k`` and ``Sᵀ q`` take
    ``operands``."""
    heads, d = q.shape[1:]

    def one(held, xs):
        qt, kt, vt, at, bt = xs
        held = held * at[:, :, None]
        err = vt - jnp.einsum("hkv,hk->hv", _round(held, operands),
                              _round(kt, operands))
        held = held + bt[:, None, None] * kt[:, :, None] * err[:, None, :]
        return held, jnp.einsum("hkv,hk->hv", _round(held, operands),
                                _round(qt, operands))

    _, out = lax.scan(one, jnp.zeros((heads, d, d), jnp.float32),
                      (q, k, v, a, b))
    return out


def _conv(x, w):
    """Each channel of ``x`` (T, C) through its causal depthwise
    convolution ``w`` (taps, C): ``y_t = sum_j w_j x_{t - taps + 1 +
    j}``, zeros before the sequence."""
    taps, t = w.shape[0], x.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(w[j] * padded[j:j + t] for j in range(taps))


@functools.partial(jax.jit, static_argnames=("dims", "operands"))
def _logits_at(blocks, norm_w, head, table, tokens, positions, dims,
               operands):
    """``logits (len(positions), V)`` of the sequence ``tokens`` (T,)
    at ``positions``, the blocks' held experts those of their leaves
    (their first id is the leaves' own: ``blocks[i]["first"]``)."""
    heads, kv_heads, eps, top_k, taps, d, neg_eigval, kinds = dims

    def mm(x, w):
        return jnp.dot(_round(x, operands), _round(w, operands))

    def swiglu(h, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)

    t = tokens.shape[0]
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for blk, kind in zip(blocks, kinds):
            h = _rms(x, blk["attn_norm"], eps)
            if kind == "nope":
                q = mm(h, blk["wq"]).reshape(t, heads, d)
                k = mm(h, blk["wk"]).reshape(t, kv_heads, d)
                v = mm(h, blk["wv"]).reshape(t, kv_heads, d)
                att = attend(q, k, v, operands).reshape(t, -1)
                att = att * jax.nn.sigmoid(mm(h, blk["wgate"]))
            else:
                mixed = jax.nn.silu(_conv(mm(h, blk["w_qkv"]),
                                          blk["conv_w"].astype(
                                              jnp.float32)))
                q, k, v = (m.reshape(t, heads, d)
                           for m in jnp.split(mixed, 3, -1))
                q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True)
                                 + 1e-6) / math.sqrt(d)
                k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True)
                                 + 1e-6)
                g = -jnp.exp(blk["A_log"])[:, None] * jax.nn.softplus(
                    (mm(mm(h, blk["w_fa"]), blk["w_fb"])
                     + blk["dt_bias"]).reshape(t, heads, d))
                b = jax.nn.sigmoid(mm(h, blk["w_beta"]))
                if neg_eigval:
                    b = 2.0 * b
                o = delta_rule(q, k, v, jnp.exp(g), b, operands)
                att = (_rms(o, blk["o_norm"], eps).reshape(t, -1)
                       * jax.nn.sigmoid(mm(mm(h, blk["w_ga"]),
                                           blk["w_gb"])))
            x = x + mm(att, blk["wout"])
            h = _rms(x, blk["ffn_norm"], eps)
            # the router over every expert; the held ones computed
            scores = jax.nn.sigmoid(mm(h, blk["router"]))
            _, chosen = lax.top_k(scores + blk["router_bias"], top_k)
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
            x = x + routed + swiglu(h, p["w_gate"], p["w_up"],
                                    p["w_down"])
        x = _rms(jnp.take(x, positions, axis=0), norm_w, eps)
        return mm(x, head)


def stack_blocks(params):
    """The blocks as they are, each with the id of its first held
    expert: a second copy of the weights would not fit beside the
    first."""
    held = params["arch"].held
    return [dict(blk, first=jnp.int32(held[0]))
            for blk in params["blocks"]]


def _padded(n, max_len):
    """The length a sequence of ``n`` is padded to (the pad sits after
    the last position, where a causal model cannot see it): ``max_len``
    in whole query blocks, or the largest power of two at most half of
    that where ``n`` fits it, so that two programs serve every length a
    run makes and its reference seldom compiles."""
    longest = -(-max_len // min(QUERY_BLOCK, max_len)) \
        * min(QUERY_BLOCK, max_len)
    shorter = 1 << ((longest // 2).bit_length() - 1)
    if n <= shorter:
        return shorter
    return -(-n // longest) * longest


def logits_after(config, params, table, prompt, served,
                 operands="float32", stacked=None):
    """Reference logits (len(served), V): row i is the distribution
    from which answered token i is drawn, i.e. at the last prompt
    position and then after each answered token but the last."""
    tokens = list(prompt) + list(served[:-1])
    first = len(prompt) - 1
    padded = _padded(len(tokens), config["serving"]["max_len"])
    n_out = config["serving"]["n_tokens"]
    ids = jnp.asarray(tokens + [0] * (padded - len(tokens)), jnp.int32)
    positions = jnp.asarray(
        [first + i for i in range(len(served))]
        + [first] * (n_out - len(served)), jnp.int32)
    out = _logits_at(stacked if stacked is not None
                     else stack_blocks(params), params["norm_w"],
                     params["head"], table, ids, positions, sizes(config),
                     operands)
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
