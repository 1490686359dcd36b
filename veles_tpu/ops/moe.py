"""A routed expert layer that drops no token and pads no expert.

The serving-side expert layer (``parallel/expert.py`` is the training
one: top-1, capacity-bounded, tokens over capacity dropped). Every
token goes to its ``top_k`` experts whatever the others chose:

    s = sigmoid(h . W_g)                    (float32, every expert)
    chosen = top_k(s + b)                   (b steers the choice only)
    w = s[chosen] / sum(s[chosen]) * scale
    y = sum_i w_i . E_chosen_i(h)           E_e = W_down(silu(W_gate h) * W_up h)

The products are grouped: the (token, expert) assignments are sorted
by expert and each expert multiplies the rows that chose it
(``jax.lax.ragged_dot``: on the TPU a grouped kernel that visits the
groups that have rows, so an expert no token chose is not read). The
layer is told which experts it holds (``held = (first, count)``): it
routes over all of them and computes the part of ``y`` that the held
ones give; the parts of disjoint shares add up to the whole layer.

The named scopes (``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.combine``, ``moe.shared``) are HLO metadata that the scope table
(``observe/xla_stats.scope_table``) carries to a traced op.
"""

import jax
import jax.numpy as jnp
from jax import lax


def route(h, router, bias, top_k, scale):
    """``(chosen (N, top_k) int32, weights (N, top_k) float32)`` for
    tokens ``h`` (N, E): scores, bias add and top-k in float32 at full
    precision (a bfloat16 score ties where a float32 one does not)."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(jnp.dot(
            h.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(scores + bias.astype(jnp.float32), top_k)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        weights = picked / jnp.sum(picked, -1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weights


def swiglu(h, p):
    """``W_down(silu(W_gate h) * W_up h)``: the dense feed-forward and
    the shared expert."""
    return (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def routed_experts(h, chosen, weights, experts, held=None, live=None):
    """The held experts' part of the layer for tokens ``h`` (N, E):
    ``(y (N, E), load (n_held,) int32)``. ``experts`` holds the
    stacked ``w_gate``/``w_up`` (count, E, F) and ``w_down``
    (count, F, E) of experts ``first .. first + count``; ``live``
    (N,) bool leaves a token out altogether (a padded position, an
    idle slot: it reads no expert and counts in no load). ``load`` is
    the assignments each held expert got."""
    count = experts["w_gate"].shape[0]
    first = 0 if held is None else held[0]
    n, top_k = chosen.shape
    with jax.named_scope("moe.dispatch"):
        local = chosen - first
        mine = (local >= 0) & (local < count)
        if live is not None:
            mine &= live[:, None]
        # an assignment that is not computed here sorts behind every
        # expert's rows, where no group reaches
        key = jnp.where(mine, local, count).reshape(-1)
        order = jnp.argsort(key)
        back = jnp.argsort(order)
        # (a comparison and a sum: a scatter-add is slow on the TPU)
        load = jnp.sum(key[:, None] == jnp.arange(count), axis=0,
                       dtype=jnp.int32)
        rows = jnp.take(h, order // top_k, axis=0)
    with jax.named_scope("moe.experts"):
        inner = jax.nn.silu(lax.ragged_dot(rows, experts["w_gate"], load)) \
            * lax.ragged_dot(rows, experts["w_up"], load)
        out = lax.ragged_dot(inner, experts["w_down"], load)
    with jax.named_scope("moe.combine"):
        # back in the tokens' order; a row past the last group holds
        # whatever the grouped product left there: selected away, not
        # multiplied by 0
        share = jnp.where(mine, weights, 0.0)
        out = jnp.take(out, back, axis=0).reshape(n, top_k, -1)
        y = jnp.sum(jnp.where(mine[..., None],
                              out.astype(jnp.float32) * share[..., None],
                              0.0), axis=1)
    return y.astype(h.dtype), load


def expert_layer(h, p, top_k, scale, held=None, live=None):
    """The whole layer for tokens ``h`` (N, E): the held experts' part
    plus the shared expert. Returns ``(y, load)``."""
    chosen, weights = route(h, p["router"], p["router_bias"], top_k, scale)
    y, load = routed_experts(h, chosen, weights, p["experts"], held, live)
    with jax.named_scope("moe.shared"):
        y = y + swiglu(h, p["shared"])
    return y, load
