"""Plain reference for the ``brumby-14b-base`` configuration.

The layers of Brumby-14B-Base (``model_type`` ``brumby``) as
``benchmark/configs/brumby-14b-base.json`` states them, in
straightforward ``jax.numpy``: float32 under
``jax.default_matmul_precision("highest")``, the whole sequence at
once, power retention in its ATTENTION FORM: a ``T x T`` matrix of
weights a head with cumulative log-gates. No feature map, no state, no
chunk, no cache, no slots, no batching, the K/V heads repeated for
their groups. None of the mathematics is taken from ``veles_tpu``.

Sizes: hidden 5120, RMSNorm (``rms_norm_eps`` 1e-6), no bias but the
gate's; 40 query heads over 8 K/V heads of ``head_dim`` 128; SwiGLU of
``intermediate_size`` 17408 (``silu``); vocabulary 151,936, the head
its own matrix (``tie_word_embeddings`` false).

- Block: ``x <- x + retention(RMSNorm(x))``, ``x <- x +
  W_down(silu(W_gate h') * W_up h')`` with ``h' = RMSNorm(x)``; after
  the last block RMSNorm, then the head.
- Retention, ``h`` the normed input, ``d`` = 128: ``q = h.W_q`` as 40
  heads, ``k = h.W_k`` and ``v = h.W_v`` as 8; ``log g = log
  sigmoid(h.W_g + b_g)``, one value a K/V head a position, float32;
  RMSNorm over the 128 of each head of q (``q_norm``) and of k
  (``k_norm``); RoPE on q and k (theta 1e6); query head ``i`` reads
  K/V head ``i // 5`` (here: K, V and the gate repeated 5 times);

      a[t, j] = (q_t . k_j)^2 / d * exp(sum_{r = j+1 .. t} log g_r)   (j <= t)
      y_t     = sum_j a[t, j] v_j / (sum_j a[t, j] + 1e-6)

  ``out = concat(y).W_o``. RoPE rotates the pairs ``(2i, 2i+1)`` of
  the 128 by ``t.theta^(-2i/128)``, as complex numbers (the
  configuration's ``departures``: the lineage's code rotates halves, a
  permutation of q and k alike).

The weights are the configuration's: bfloat16 values made here on the
device from the seed in one jitted call (``init_params``), under the
leaf names of ``veles_tpu.parallel.blocks.Retention`` (``Grouped``'s
plus ``wg``, ``bg``), with the architecture riding in
``params["arch"]``. The gate's bias is float32 and drawn so that a
head's memory ``-1 / log g`` is log-uniform over 16 .. 4,096
positions (the configuration's ``assumed``). The reference widens the
same values to float32, a block at a time; ``operands`` instead rounds
both operands of every matrix product (weights and activations, the
scores and the weighted sum included) to a lower type first:
``"float8_e4m3fn"`` is the control the configuration names.

``served_gaps`` is the comparison: for a prompt and the tokens the
server answered with (greedy), the reference's logits at each answered
position and how far the answered token's logit lies below the
reference's best there.
"""

import functools
import math
import sys

import jax
import jax.numpy as jnp

#: sequences are padded to a multiple of this so that a handful of
#: programs serve every length (the pad sits after the last position,
#: where a causal model cannot see it)
PAD = 256
#: the head's columns widened to float32 at a time
HEAD_PARTS = 8
#: a head's memory, in positions, is drawn log-uniform between these
MEMORY = (16.0, 4096.0)
EPS = 1e-6


def sizes(config):
    """The sizes the forward needs, hashable."""
    return (config["n_head"], config["num_key_value_heads"],
            config["rms_norm_eps"], float(config["rope_theta"]))


def init_params(seed, config):
    """``(params, embed_table)`` in bfloat16 on the default device:
    matrices N(0, 1/fan_in), norm gains 1 + N(0, 0.02), table N(0,
    0.02), the gate's bias float32 with ``-1 / log sigmoid(b)``
    log-uniform over ``MEMORY``. Every leaf is drawn on its own, so
    nothing twice its size ever stands."""
    from veles_tpu.parallel.blocks import Arch

    e, v = config["hidden_size"], config["vocab_size"]
    heads, kv_heads = config["n_head"], config["num_key_value_heads"]
    head_dim, hidden = config["head_dim"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    # the architecture rides with the parameters (a static node); a
    # program that knows no such kind, or another degree, refuses
    # before any weight is made
    arch = Arch(
        layers="ret", eps=config["rms_norm_eps"], kv_heads=kv_heads,
        rope_theta=float(config["rope_theta"]),
        power=config["retention_power"],
        prefill_tokens=config["serving"].get("prefill_tokens", 0))
    bf = jnp.bfloat16

    @jax.jit
    def make(key):
        count = iter(range(1 << 20))

        def normal(shape, scale, mean=0.0, dtype=bf):
            k = jax.random.fold_in(key, next(count))
            return (mean + scale * jax.random.normal(k, shape, jnp.float32)
                    ).astype(dtype)

        def mat(a, b):
            return normal((a, b), 1.0 / math.sqrt(a))

        def gain(n):
            return normal((n,), 0.02, 1.0)

        def memory_bias():
            k = jax.random.fold_in(key, next(count))
            u = jax.random.uniform(k, (kv_heads,), jnp.float32)
            log_g = -1.0 / (MEMORY[0] * (MEMORY[1] / MEMORY[0]) ** u)
            # the b with log sigmoid(b) = log_g
            return log_g - jnp.log(-jnp.expm1(log_g))

        blocks = []
        for _ in range(layers):
            blocks.append({
                "attn_norm": gain(e), "ffn_norm": gain(e),
                "wq": mat(e, heads * head_dim),
                "wk": mat(e, kv_heads * head_dim),
                "wv": mat(e, kv_heads * head_dim),
                "q_norm": gain(head_dim), "k_norm": gain(head_dim),
                "wg": mat(e, kv_heads), "bg": memory_bias(),
                "wout": mat(heads * head_dim, e),
                "w_gate": mat(e, hidden), "w_up": mat(e, hidden),
                "w_down": mat(hidden, e)})
        return {"blocks": blocks, "norm_w": gain(e),
                "head": mat(e, v)}, normal((v, e), 0.02)

    # the counter-mode generator the TPU has in hardware ("rbg"): the
    # default threefry takes minutes for 4.2 G numbers there
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


def retention(q, k, v, log_g, operands="float32"):
    """The attention form: ``q`` (T, H, d), ``k``, ``v`` (T, H, d),
    ``log_g`` (T, H) -> (T, H, d)."""
    t, d = q.shape[0], q.shape[-1]
    s = jnp.einsum("qhd,khd->hqk", _round(q, operands),
                   _round(k, operands))
    reach = jnp.cumsum(log_g, axis=0).T                     # (H, T)
    causal = jnp.tril(jnp.ones((t, t), bool))
    # exp(sum_{r = j+1 .. t} log g_r); above the diagonal nothing
    decay = jnp.exp(jnp.where(
        causal, reach[:, :, None] - reach[:, None, :], -jnp.inf))
    a = s * s / d * decay
    num = jnp.einsum("hqk,khd->qhd", _round(a, operands),
                     _round(v, operands))
    return num / (jnp.sum(a, -1).T[..., None] + EPS)


@functools.partial(jax.jit, static_argnames=("dims", "operands"))
def _logits_at(blocks, norm_w, head, table, tokens, positions, dims,
               operands):
    """``logits (len(positions), V)`` of the sequence ``tokens`` (T,)
    at ``positions``."""
    heads, kv_heads, eps, theta = dims

    def mm(x, w):
        return jnp.dot(_round(x, operands), _round(w, operands))

    t = tokens.shape[0]
    x = jnp.take(table, tokens, axis=0).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        for blk in blocks:
            h = _rms(x, blk["attn_norm"], eps)
            q = mm(h, blk["wq"]).reshape(t, heads, -1)
            k = mm(h, blk["wk"]).reshape(t, kv_heads, -1)
            v = mm(h, blk["wv"]).reshape(t, kv_heads, -1)
            log_g = jax.nn.log_sigmoid(mm(h, blk["wg"]) + blk["bg"])
            q = _rope(_rms(q, blk["q_norm"], eps), theta)
            k = _rope(_rms(k, blk["k_norm"], eps), theta)
            # query head i reads K/V head i // (heads // kv_heads)
            k, v, log_g = (jnp.repeat(a, heads // kv_heads, axis=1)
                           for a in (k, v, log_g))
            y = retention(q, k, v, log_g, operands)
            x = x + mm(y.reshape(t, -1), blk["wout"])
            h = _rms(x, blk["ffn_norm"], eps)
            x = x + mm(jax.nn.silu(mm(h, blk["w_gate"]))
                       * mm(h, blk["w_up"]), blk["w_down"])
        x = _rms(jnp.take(x, positions, axis=0), norm_w, eps)
        step = -(-head.shape[1] // HEAD_PARTS)
        return jnp.concatenate(
            [mm(x, head[:, at:at + step])
             for at in range(0, head.shape[1], step)], -1)


def stack_blocks(params):
    """The blocks as they are: a second, stacked copy of 5.3 GB would
    not fit beside the first."""
    return params["blocks"]


def logits_after(config, params, table, prompt, served,
                 operands="float32", stacked=None):
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
    out = _logits_at(params["blocks"], params["norm_w"], params["head"],
                     table, ids, positions, sizes(config), operands)
    return out[:len(served)]


def _gaps(want, tokens):
    """How far each of ``tokens``' logits lies below the best of its
    row of ``want``."""
    picked = jnp.take_along_axis(want, tokens[:, None], 1)[:, 0]
    return jnp.max(want, -1) - picked


def served_gaps(config, params, table, prompt, served, stacked=None):
    """For each answered token, how far its reference logit lies below
    the reference's best at that position (0 where the reference would
    have answered the same): a float32 vector on the host. The mean
    and the widest go to standard error, for the calibration."""
    import numpy

    gaps = numpy.asarray(_gaps(
        logits_after(config, params, table, prompt, served),
        jnp.asarray(served, jnp.int32)))
    print("reference: served gap mean %.4f widest token %.4f over %d "
          "tokens" % (gaps.mean(), gaps.max(), len(gaps)),
          file=sys.stderr)
    return gaps


def control_gaps(config, params, table, prompt, served, operands,
                 stacked=None):
    """The control: at each position of the same prompt and answered
    tokens, the gap (under the reference) of the token that the lower
    precision ``operands`` puts first."""
    import numpy

    want = logits_after(config, params, table, prompt, served)
    low = logits_after(config, params, table, prompt, served,
                       operands=operands)
    gaps = numpy.asarray(_gaps(want, jnp.argmax(low, -1)))
    print("reference: control gap mean %.4f widest token %.4f over %d "
          "tokens" % (gaps.mean(), gaps.max(), len(gaps)),
          file=sys.stderr)
    return gaps
