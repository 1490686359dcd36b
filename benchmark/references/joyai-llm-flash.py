"""Plain reference for the ``joyai-llm-flash`` configuration.

The layers of JoyAI-LLM-Flash (DeepSeek-V3's) as
``benchmark/configs/joyai-llm-flash.json`` states them, in
straightforward ``jax.numpy``: float32 under
``jax.default_matmul_precision("highest")``, the whole sequence at
once, **expanded** attention, no cache, no slots, no batching, the
experts as a loop. None of the mathematics is taken from ``veles_tpu``.

Sizes: hidden 2048, 32 heads, ``q_lora_rank`` 1536, ``kv_lora_rank``
512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64, ``v_head_dim``
128, ``rms_norm_eps`` 1e-6, ``rope_theta`` 32,000,000, ``rope_scaling``
null, ``rope_interleave`` true, ``intermediate_size`` 7168,
``moe_intermediate_size`` 768, 256 routed experts, 8 a token, 1
shared, ``first_k_dense_replace`` 1, ``scoring_func`` sigmoid,
``topk_method`` noaux_tc, ``n_group`` 1, ``topk_group`` 1,
``norm_topk_prob`` true, ``routed_scaling_factor`` 2.5, vocabulary
129,280, head untied, no bias anywhere.

- Block: ``x <- x + Attn(RMSNorm(x))``, ``x <- x + FFN(RMSNorm(x))``;
  final RMSNorm, then the head.
- Latent attention, ``h`` the normed input at position ``t``:
  ``c_q = RMSNorm(h.W_qa)`` (1536); ``q = c_q.W_qb`` -> 32 x (128 nope
  + 64 rope); ``q_r = RoPE_t(q_rope)``. ``[c_kv | k_r] = h.W_kva``
  (512 + 64); ``c = RMSNorm(c_kv)``; ``k_rope = RoPE_t(k_r)``, one for
  all heads. (The served cache row of a position is ``[c | k_rope]``,
  576 values; the reference keeps no cache.) ``[k_nope | v] = c.W_kvb``
  -> 32 x (128 + 128); ``score = (q_nope.k_nope + q_r.k_rope) /
  sqrt(192)``, causal softmax in float32, ``o = sum p.v``,
  ``out = o.W_o`` (4096 -> 2048). RoPE rotates the pairs ``(2i, 2i+1)``
  of the 64 rope values by ``t.theta^(-2i/64)`` (``rope_interleave``):
  here as complex numbers ``(x_2i + i x_2i+1) . exp(i t theta^(-2i/64))``.
- Layer 0's FFN: SwiGLU, ``W_down(silu(W_gate h) * W_up h)``, width
  7168.
- Layers 1...: ``s = sigmoid(h.W_g)`` (256, float32); chosen = top-8 of
  ``s + b`` (``b`` the ``e_score_correction_bias``; one group, so no
  group limit); ``w = s[chosen] / sum s[chosen] . 2.5`` (``b`` is not
  in the weights); ``y = sum_i w_i.E_i(h) + E_shared(h)``, every
  expert a SwiGLU of width 768. Every expert is computed for every
  token and weighted by ``w`` or 0, a group of experts at a time,
  widened from the bfloat16 leaves a group at a time: no token can be
  dropped, and the reference fits beside the weights it checks.

Departure (the configuration's file says the same): multi-token
prediction, ``num_nextn_predict_layers`` 1, is a draft module beside
the model; it is not served and not in the reference, as the published
inference code leaves it out.

The weights are the configuration's: bfloat16 values made here on the
device from the seed in one jitted call (``init_params``), under the
leaf names of ``veles_tpu.parallel.blocks`` (``Latent``, ``ops/moe``),
with the architecture riding in ``params["arch"]``. The reference
widens those same values to float32; ``operands`` instead rounds both
operands of every product (weights and activations, attention and
router included) to a lower type first: ``"float8_e4m3fn"`` is the
control the configuration names.

``served_gaps`` is the comparison: for a prompt and the tokens the
server answered with (greedy), the reference's logits at each answered
position, how far the answered token's logit lies below the
reference's best there, and of that the **mean over the request's
answered tokens**. The mean and not the widest, because a top-8 choice
flips between bfloat16 and float32 at a near tie, and with 256 experts
near ties are common: the reference itself with bfloat16 operands
changes the expert set of 7%, 18%, 29% and 38% of the tokens in the four
expert layers (errors grow with depth), its logits then differ from
float32's by 0.21 of their spread, and the widest gap over 64 positions
is 1.6 where the float8 control's is 2.2: no limit lies between. The
means are 0.12 and 0.87 (the configuration's file has the readings on
the chip). What the mean cannot see is one token altered in a long
answer (4 / n of a gap); a piece of the mathematics left out moves
every token (``tests/test_latent_moe.py`` plants three).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

#: sequences are padded to a multiple of this so that a handful of
#: programs serve every length (the pad sits after the last position,
#: where a causal model cannot see it)
PAD = 128
#: experts widened to float32 at a time
GROUP = 8


def sizes(config):
    """The sizes the forward needs, hashable."""
    return tuple(config[key] for key in (
        "n_head", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "rms_norm_eps", "rope_theta", "num_experts_per_tok",
        "routed_scaling_factor"))


def init_params(seed, config):
    """``(params, embed_table)`` in bfloat16 on the default device:
    matrices N(0, 1/fan_in), norm gains 1 + N(0, 0.02), the router's
    selection bias N(0, 0.02) in float32, table N(0, 0.02). Every leaf
    is drawn on its own, so nothing twice its size ever stands."""
    from veles_tpu.parallel.blocks import Arch

    e, v = config["hidden_size"], config["vocab_size"]
    heads = config["n_head"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope_dim = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v_dim = config["v_head_dim"]
    experts, width = config["n_routed_experts"], \
        config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"]
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
        for layer in range(config["num_hidden_layers"]):
            blk = {"attn_norm": gain(e), "wq_a": mat(e, q_rank),
                   "q_norm": gain(q_rank),
                   "wq_b": mat(q_rank, heads * (nope + rope_dim)),
                   "wkv_a": mat(e, kv_rank + rope_dim),
                   "kv_norm": gain(kv_rank),
                   "wkv_b": mat(kv_rank, heads * (nope + v_dim)),
                   "wout": mat(heads * v_dim, e), "ffn_norm": gain(e)}
            if layer < dense:
                blk.update(swiglu(config["intermediate_size"]))
            else:
                blk.update(
                    router=mat(e, experts),
                    router_bias=normal((experts,), 0.02,
                                       dtype=jnp.float32),
                    experts=swiglu(width, (experts,)),
                    shared=swiglu(width * config["n_shared_experts"]))
            blocks.append(blk)
        params = {"blocks": blocks, "norm_w": gain(e), "head": mat(e, v)}
        return params, normal((v, e), 0.02)

    # the counter-mode generator the TPU has in hardware ("rbg"): the
    # default threefry takes minutes for 5.6 G numbers there
    seed = int(seed)
    params, table = make(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31))
    # the architecture rides with the parameters (a static node)
    params["arch"] = Arch(
        attention="mla", eps=config["rms_norm_eps"], kv_rank=kv_rank,
        nope_dim=nope, rope_dim=rope_dim,
        rope_theta=float(config["rope_theta"]),
        top_k=config["num_experts_per_tok"],
        route_scale=config["routed_scaling_factor"],
        prefill_tokens=config["serving"].get("prefill_tokens", 0))
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
    """``x`` (T, ..., R) at positions 0..T-1, pairs as complex numbers."""
    t, r = x.shape[0], x.shape[-1]
    turn = jnp.exp(1j * jnp.arange(t)[:, None]
                   * theta ** (-jnp.arange(0, r, 2) / r))
    turn = turn.reshape((t,) + (1,) * (x.ndim - 2) + (r // 2,))
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    turned = (pairs[..., 0] + 1j * pairs[..., 1]) * turn
    return jnp.stack([turned.real, turned.imag], -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dims", "operands"))
def _logits_at(blocks, norm_w, head, table, tokens, positions, dims,
               operands):
    """``(logits (len(positions), V), chosen)`` of the sequence
    ``tokens`` (T,) at ``positions``; ``chosen`` (expert layers, T,
    top_k) are the experts each token was routed to."""
    heads, kv_rank, nope, rope_dim, eps, theta, top_k, scale = dims

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
            q = mm(_rms(mm(h, blk["wq_a"]), blk["q_norm"], eps),
                   blk["wq_b"]).reshape(t, heads, nope + rope_dim)
            q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
            kv = mm(h, blk["wkv_a"])
            c = _rms(kv[:, :kv_rank], blk["kv_norm"], eps)
            k_rope = _rope(kv[:, kv_rank:], theta)
            up = mm(c, blk["wkv_b"]).reshape(t, heads, -1)
            k_nope, v = up[..., :nope], up[..., nope:]
            s = (jnp.einsum("qhd,khd->hqk", _round(q_nope, operands),
                            _round(k_nope, operands))
                 + jnp.einsum("qhd,kd->hqk", _round(q_rope, operands),
                              _round(k_rope, operands))) \
                / math.sqrt(nope + rope_dim)
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
                picked / jnp.sum(picked, -1, keepdims=True) * scale)
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
            x = x + routed + swiglu(h, blk["shared"])
        x = _rms(jnp.take(x, positions, axis=0), norm_w, eps)
        return mm(x, head), jnp.stack(chosen_all)


def stack_blocks(params):
    """The blocks as they are: they differ in kind, and a second copy
    of 10 GiB would not fit beside the first."""
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
    out, chosen = _logits_at(params["blocks"], params["norm_w"],
                             params["head"], table, ids, positions,
                             sizes(config), operands)
    if with_chosen:
        return out[:len(served)], chosen[:, :len(tokens)]
    return out[:len(served)]


def served_gaps(config, params, table, prompt, served, stacked=None):
    """How far an answered token's reference logit lies below the
    reference's best at its position (0 where the reference would
    have answered the same), the mean over the request's answered
    tokens: a float32 vector of one number on the host."""
    import numpy

    logits = logits_after(config, params, table, prompt, served)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(served, jnp.int32)[:, None], 1)[:, 0]
    return numpy.asarray(jnp.mean(jnp.max(logits, -1) - picked))[None]


def control_gaps(config, params, table, prompt, served, operands,
                 stacked=None):
    """The control: at each position of the same prompt and answered
    tokens, the gap (under the reference) of the token that the lower
    precision ``operands`` puts first; the mean, as ``served_gaps``."""
    import numpy

    want = logits_after(config, params, table, prompt, served)
    low = logits_after(config, params, table, prompt, served,
                       operands=operands)
    picked = jnp.take_along_axis(want, jnp.argmax(low, -1)[:, None],
                                 1)[:, 0]
    return numpy.asarray(jnp.mean(jnp.max(want, -1) - picked))[None]


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
