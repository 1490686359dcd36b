"""Plain reference for the ``gpt2-medium`` configuration.

The GPT-2 block as ``benchmark/configs/gpt2-medium.json`` states it,
in straightforward ``jax.numpy``: float32 with every product at
``Precision.HIGHEST``, the whole sequence at once, no cache, no slots,
no batching; nothing imported from ``veles_tpu`` and nothing taken from
it. Pre-LN blocks: ``x += proj(attn(ln1(x)))`` with a fused biased
``c_attn`` (E -> 3E, split q|k|v, ``n_head`` heads, causal softmax of
``q.k / sqrt(head_dim)``), ``x += fc2(gelu_new(fc1(ln2(x))))``, final
layer norm, vocabulary head. The configuration's departures are
mirrored: no learned position embedding, and an untied head.

The weights are the configuration's: bfloat16 values made here on the
device from the seed in one jitted call (``init_params``), under the
leaf names ``GenerateAPI`` expects. The reference widens those same
values to float32; ``operands`` instead rounds both operands of every
product (weights and activations, attention included) to a lower type
first: ``"float8_e4m3fn"`` is the control the configuration names.

``served_gaps`` is the comparison: for a prompt and the tokens the
server answered with (greedy), the reference's logits at each answered
position, and how far the answered token's logit lies below the
reference's best there.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
#: sequences are padded to a multiple of this so that a handful of
#: programs serve every length (the pad sits after the last position,
#: where a causal model cannot see it)
PAD = 128


def init_params(seed, config):
    """``(params, embed_table)`` in bfloat16 on the default device:
    matrices N(0, 1/fan_in), biases and layer-norm offsets N(0, 0.02),
    layer-norm gains 1 + N(0, 0.02), table N(0, 0.02)."""
    e, v = config["n_embd"], config["vocab_size"]
    hidden, layers = config["n_inner"], config["n_layer"]
    bf = jnp.bfloat16

    @jax.jit
    def make(key):
        count = iter(range(1 << 20))

        def normal(shape, scale, mean=0.0):
            k = jax.random.fold_in(key, next(count))
            return (mean + scale * jax.random.normal(k, shape, jnp.float32)
                    ).astype(bf)

        def mat(a, b, lead=()):
            return normal(lead + (a, b), 1.0 / math.sqrt(a))

        # every leaf of all the layers in one draw, then cut by layer
        lead = (layers,)
        stacked = {
            "ln1_w": normal(lead + (e,), 0.02, 1.0),
            "ln1_b": normal(lead + (e,), 0.02),
            "wqkv": mat(e, 3 * e, lead),
            "bqkv": normal(lead + (3 * e,), 0.02),
            "wout": mat(e, e, lead), "bout": normal(lead + (e,), 0.02),
            "ln2_w": normal(lead + (e,), 0.02, 1.0),
            "ln2_b": normal(lead + (e,), 0.02),
            "w1": mat(e, hidden, lead),
            "b1": normal(lead + (hidden,), 0.02),
            "w2": mat(hidden, e, lead), "b2": normal(lead + (e,), 0.02)}
        blocks = [{name: leaf[i] for name, leaf in stacked.items()}
                  for i in range(layers)]
        params = {"blocks": blocks,
                  "lnf_w": normal((e,), 0.02, 1.0),
                  "lnf_b": normal((e,), 0.02), "head": mat(e, v)}
        return params, normal((v, e), 0.02)

    # the counter-mode generator the TPU has in hardware ("rbg"): the
    # default threefry takes over a minute for 355 M numbers there
    seed = int(seed)
    return make(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF, impl="rbg"), seed >> 31))


def _round(x, operands):
    x = x.astype(jnp.float32)
    if operands == "float32":
        return x
    return x.astype(jnp.dtype(operands)).astype(jnp.float32)


def _ln(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _mm(x, w, operands):
    return jnp.dot(_round(x, operands), _round(w, operands),
                   precision=HIGHEST)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("heads", "eps", "operands"))
def _logits_at(stacked, lnf_w, lnf_b, head, table, tokens, positions,
               heads, eps, operands):
    """Logits (len(positions), V) of the sequence ``tokens`` (T,) at
    ``positions``; ``stacked`` holds every block's leaves stacked on a
    leading layer axis."""
    t = tokens.shape[0]
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    e = x.shape[-1]
    d = e // heads
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, p):
        h = _ln(x, p["ln1_w"], p["ln1_b"], eps)
        qkv = _mm(h, p["wqkv"], operands) + p["bqkv"].astype(jnp.float32)
        q, k, v = (a.reshape(t, heads, d) for a in jnp.split(qkv, 3, -1))
        s = jnp.einsum("qhd,khd->hqk", _round(q, operands),
                       _round(k, operands),
                       precision=HIGHEST) / math.sqrt(d)
        p_att = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        att = jnp.einsum("hqk,khd->qhd", _round(p_att, operands),
                         _round(v, operands), precision=HIGHEST)
        x = x + _mm(att.reshape(t, e), p["wout"], operands) \
            + p["bout"].astype(jnp.float32)
        h = _ln(x, p["ln2_w"], p["ln2_b"], eps)
        h = _gelu_new(_mm(h, p["w1"], operands)
                      + p["b1"].astype(jnp.float32))
        x = x + _mm(h, p["w2"], operands) + p["b2"].astype(jnp.float32)
        return x, None

    x, _ = lax.scan(block, x, stacked)
    x = _ln(jnp.take(x, positions, axis=0), lnf_w, lnf_b, eps)
    return _mm(x, head, operands)


def stack_blocks(params):
    return jax.tree.map(lambda *leaves: jnp.stack(leaves),
                        *params["blocks"])


def logits_after(config, params, table, prompt, served,
                 operands="float32", stacked=None):
    """Reference logits (len(served), V): row i is the distribution
    from which answered token i is drawn, i.e. at the last prompt
    position and then after each answered token but the last."""
    if stacked is None:
        stacked = stack_blocks(params)
    tokens = list(prompt) + list(served[:-1])
    first = len(prompt) - 1
    padded = -(-len(tokens) // PAD) * PAD
    n_out = config["serving"]["n_tokens"]
    ids = jnp.asarray(tokens + [0] * (padded - len(tokens)), jnp.int32)
    positions = jnp.asarray(
        [first + i for i in range(len(served))]
        + [first] * (n_out - len(served)), jnp.int32)
    out = _logits_at(stacked, params["lnf_w"], params["lnf_b"],
                     params["head"], table, ids, positions,
                     config["n_head"], config["layer_norm_epsilon"],
                     operands)
    return out[:len(served)]


def served_gaps(config, params, table, prompt, served, stacked=None):
    """For each answered token, how far its reference logit lies below
    the reference's best at that position (0 where the reference would
    have answered the same): a float32 vector on the host."""
    import numpy

    logits = logits_after(config, params, table, prompt, served,
                          stacked=stacked)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(served, jnp.int32)[:, None], 1)[:, 0]
    return numpy.asarray(jnp.max(logits, -1) - picked)


def control_gaps(config, params, table, prompt, served, operands,
                 stacked=None):
    """The control: at each position of the same prompt and answered
    tokens, the gap (under the reference) of the token that the lower
    precision ``operands`` puts first."""
    import numpy

    want = logits_after(config, params, table, prompt, served,
                        stacked=stacked)
    low = logits_after(config, params, table, prompt, served,
                       operands=operands, stacked=stacked)
    picked = jnp.take_along_axis(want, jnp.argmax(low, -1)[:, None],
                                 1)[:, 0]
    return numpy.asarray(jnp.max(want, -1) - picked)
