"""The gated delta rule with a decay a channel (Kimi Delta Attention).

A head's past is a float32 state ``S`` (``d_k x d_v``) that each
position decays channel by channel, then corrects towards its value
along its key, then reads with its query (Yang, Kautz, Hatamizadeh,
"Gated Delta Networks", arXiv:2412.06464; the decay a key channel of
Kimi Linear, arXiv:2510.26692):

    S_t = (I - b_t k_t k_tᵀ) Diag(a_t) S_{t-1} + b_t k_t v_tᵀ
    o_t = S_tᵀ q_t

``a_t = exp(g_t)``, ``g_t`` (``d_k``) <= 0 the log-decay of each key
channel, ``b_t`` a head's write strength (in (0, 2) where the model lets
``I - b k kᵀ`` have a negative eigenvalue), ``q`` and ``k`` of unit
length (``q`` scaled by ``1/sqrt(d_k)``): the caller's.

**The two programs.** :func:`prompt` runs whole right-padded sequences
in chunks of ``CHUNK`` positions: inside a chunk the WY form (below),
between chunks a ``lax.scan`` that carries ``S``; its second result is
the state after each row's TRUE length, from the same scan, so no
chunk's state is kept. :func:`step` is the recurrence for one new
position a slot in ``jax.numpy``: each slot's state decayed, corrected,
read with the query. On a v5e at 128 slots of 64 heads of 128 it takes
2.46 ms a layer (0.54 GB of state read and written: 53% of the chip's
819 GB/s), where a Pallas kernel of a slot and 16 heads a grid step took
4.42 (PERF.md §5), so the step has no kernel of its own.

**The WY form of a chunk.** With ``G_t`` the decay summed from the
chunk's start to ``t`` (its own included) and ``S_0`` the state the
chunk begins with, the corrections ``e_t = b_t (v_t - S_{t-1}ᵀ (a_t ⊙
k_t))`` solve one unit lower-triangular system

    (I + L) E = B V - B K⁺ S_0,    L[t, i] = b_t Σ_c k_t,c k_i,c exp(G_t,c - G_i,c)  (i < t)

(``B`` the write strengths on the diagonal, ``K⁺`` the rows ``k_t ⊙
exp(G_t)``), solved by forward substitution
(``lax.linalg.triangular_solve``) for both right-hand sides at once:
``U = (I + L)⁻¹ B V``, ``W = (I + L)⁻¹ B K⁺``, ``E = U - W S_0``. Then

    o_t = (q_t ⊙ exp(G_t))ᵀ S_0 + Σ_{i <= t} E_i Σ_c q_t,c k_i,c exp(G_t,c - G_i,c)
    S_r = exp(G_r) ⊙ S_0 + Σ_{i <= r} (k_i ⊙ exp(G_r - G_i)) E_iᵀ

at ``r`` the chunk's last position, or a row's last live one. Every
exponent is a decay between two positions, never positive: a pair
``(t, i)`` in one sub-chunk of ``_SUB`` positions takes its own
exponents channel by channel; a pair across sub-chunks takes the end of
``i``'s sub-chunk as the reference, ``exp(G_t - R) exp(R - G_i)``,
both factors at most 1. No ``1/exp(G)`` is formed, so a channel that
decays by ``e^-9`` a position (``A_log`` up to ``log 16``) loses no
precision and overflows nothing.

Everything that touches the state is float32 at ``Precision.HIGHEST``
(the TPU's default float32 product rounds operands to bfloat16, and the
state sums thousands of terms).
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

#: positions :func:`prompt` takes in the WY form at once
CHUNK = 64
#: positions inside a chunk whose pairs take their exponents channel by
#: channel (``c x c x d`` would be 0.5 M values a chunk and head)
_SUB = 16
#: heads :func:`prompt` runs at once (the others wait in a ``fori_loop``):
#: a chunk's pair tensors of all 64 heads of an 8,192-position row would
#: not fit beside the weights
HEADS_AT_ONCE = 8

HIGHEST = lax.Precision.HIGHEST


def _dot(spec, *xs):
    return jnp.einsum(spec, *xs, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _pairs(x, k, G):
    """``P[..., t, i] = Σ_c x_t,c k_i,c exp(G_t,c - G_i,c)`` for ``i <=
    t`` (0 above the diagonal) over the chunks of ``x``, ``k``, ``G``
    (..., c, d) float32, ``c`` whole sub-chunks."""
    c, d = x.shape[-2:]
    s = min(_SUB, c)
    nb = c // s
    lead = x.shape[:-2]
    xb, kb, Gb = (a.reshape(lead + (nb, s, d)) for a in (x, k, G))
    # inside a sub-chunk: each pair's own exponents
    own = jnp.tril(jnp.ones((s, s), bool))
    gap = Gb[..., :, None, :] - Gb[..., None, :, :]       # (.., nb, s, s, d)
    inside = jnp.sum(
        xb[..., :, None, :] * kb[..., None, :, :]
        * jnp.exp(jnp.where(own[:, :, None], gap, -jnp.inf)), -1)
    out = inside[..., :, :, None, :] * jnp.eye(nb)[:, None, :, None]
    if nb > 1:
        # across: the end of i's sub-chunk as the reference
        ref = Gb[..., -1, :]                                # (.., nb, d)
        left = kb * jnp.exp(ref[..., :, None, :] - Gb)      # (.., nb_i, s, d)
        later = (jnp.arange(nb)[:, None] > jnp.arange(nb)[None, :])
        right = xb[..., :, None, :, :] * jnp.exp(jnp.where(
            later[:, :, None, None],
            Gb[..., :, None, :, :] - ref[..., None, :, None, :], -jnp.inf))
        across = _dot("...baid,...ajd->...biaj", right, left)
        out = out + across                                  # 0 where not later
    return out.reshape(lead + (c, c))


def _chunk_parts(q, k, v, g, beta, count):
    """What a chunk gives the scan, for every chunk at once: ``(U, W,
    Q, P, K, decay)`` of ``q``, ``k``, ``v``, ``g`` (..., c, d) and
    ``beta`` (..., c), ``count`` (...) the chunk's live positions:
    ``E = U - W S_0``, ``o = Q S_0 + P E``, ``S <- decay ⊙ S_0 + Kᵀ E``
    (the state at the chunk's last live position; unchanged where the
    chunk has none)."""
    c = q.shape[-2]
    # the sums as one product with a triangle of ones: XLA's cumsum on
    # the TPU is a reduce-window of the chunk's length at every position
    G = _dot("ts,...sd->...td", jnp.tril(jnp.ones((c, c), jnp.float32)), g)
    kk = jnp.tril(_pairs(k, k, G), -1) * beta[..., :, None]
    rhs = jnp.concatenate([v * beta[..., None],
                           k * jnp.exp(G) * beta[..., None]], -1)
    solved = lax.linalg.triangular_solve(
        kk, rhs, left_side=True, lower=True, unit_diagonal=True)
    d = v.shape[-1]
    U, W = solved[..., :d], solved[..., d:]
    P = _pairs(q, k, G)
    # the last live position's decays (0, none at all: nothing decays),
    # picked by a mask and a sum (a gather of them is slow on the TPU)
    at = jnp.arange(c)
    last = jnp.sum(jnp.where((at == count[..., None] - 1)[..., None], G,
                             0.0), axis=-2, keepdims=True)      # (.., 1, d)
    live = (at < count[..., None])[..., None]
    K = jnp.where(live, k * jnp.exp(jnp.where(live, last - G, -jnp.inf)),
                  0.0)
    return U, W, q * jnp.exp(G), P, K, jnp.exp(last[..., 0, :])


def _chunks(x, c):
    """``(B, T, ...)`` -> ``(T // c, B, c, ...)``."""
    return jnp.moveaxis(x.reshape((x.shape[0], -1, c) + x.shape[2:]), 1, 0)


def _run(q, k, v, g, beta, count, c):
    """:func:`prompt` of one group of heads: ``q``, ``k``, ``v``, ``g``
    (B, T, Hg, d), ``beta`` (B, T, Hg), ``count`` (T // c, B) the live
    positions of each chunk of each row -> ``(y (B, T, Hg, d) in
    ``v``'s type, S (B, Hg, d, d) float32)``."""
    batch, t, heads, d = q.shape
    # (n, B, Hg, c, ...) float32: chunks lead, heads beside the batch
    parts = [jnp.swapaxes(_chunks(a.astype(jnp.float32), c), 2, 3)
             for a in (q, k, v, g)]
    b = jnp.swapaxes(_chunks(beta.astype(jnp.float32), c), 2, 3)
    U, W, Q, P, K, decay = _chunk_parts(
        *parts, b, jnp.broadcast_to(count[:, :, None],
                                    count.shape + (heads,)))

    def body(held, xs):
        u, w, qq, p, kk, dec = xs
        err = u - _dot("bhcd,bhde->bhce", w, held)
        out = _dot("bhcd,bhde->bhce", qq, held) \
            + _dot("bhci,bhie->bhce", p, err)
        held = dec[..., :, None] * held + _dot("bhcd,bhce->bhde", kk, err)
        return held, out.astype(v.dtype)

    held, y = lax.scan(body, jnp.zeros((batch, heads, d, d), jnp.float32),
                       (U, W, Q, P, K, decay))
    # (n, B, Hg, c, d) -> (B, T, Hg, d)
    return jnp.moveaxis(jnp.swapaxes(y, 2, 3), 0, 1).reshape(
        batch, t, heads, d), held


def prompt(q, k, v, g, beta, live=None):
    """Whole sequences: ``q``, ``k``, ``v`` (B, T, H, d) (``q`` and ``k``
    of unit length, ``q`` scaled), ``g`` (B, T, H, d) float32 log-decays,
    ``beta`` (B, T, H) float32, ``live`` (B, T) bool marking a
    right-padded row's own positions (None: all of them) -> ``(y (B, T,
    H, d) in ``v``'s type, S (B, H, d, d) float32)``: the outputs
    (causal, so a row's padding changes nothing before it) and the state
    after each row's true length. Each group of ``HEADS_AT_ONCE`` heads
    is widened to float32 and written into the answer in its turn."""
    batch, t, heads, d = q.shape
    c = min(CHUNK, -(-t // _SUB) * _SUB)
    pad = -t % c
    lengths = jnp.full((batch,), t, jnp.int32) if live is None \
        else jnp.sum(live, -1).astype(jnp.int32)
    ins = (q, k, v, g, beta)
    if pad:
        ins = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
               for a in ins]
    starts = jnp.arange((t + pad) // c) * c
    count = jnp.clip(lengths[None, :] - starts[:, None], 0, c)   # (n, B)
    group = math.gcd(heads, HEADS_AT_ONCE)

    def one(i, out):
        y, held = _run(*(lax.dynamic_slice_in_dim(a, i * group, group, 2)
                         for a in ins), count, c)
        return (lax.dynamic_update_slice_in_dim(out[0], y, i * group, 2),
                lax.dynamic_update_slice_in_dim(out[1], held, i * group, 1))

    y, held = lax.fori_loop(0, heads // group, one, (
        jnp.zeros((batch, t + pad, heads, d), v.dtype),
        jnp.zeros((batch, heads, d, d), jnp.float32)))
    return y[:, :t], held


# -- the decode step -----------------------------------------------------------

def step(q, k, v, g, beta, held, active):
    """One new position a slot: ``q``, ``k``, ``v`` (S, H, d), ``g``
    (S, H, d) float32 log-decays, ``beta`` (S, H) float32, the slots'
    state ``held`` (S, H, d, d) float32, ``active`` (S,) bool -> ``(y
    (S, H, d) float32, held)``. A lane that is not ``active`` keeps its
    state bit for bit (decay 1, nothing written) and its answer is no
    one's."""
    decay = jnp.where(active[:, None, None], jnp.exp(g), 1.0)
    beta = jnp.where(active[:, None], beta, 0.0)
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    held = held * decay[..., None]
    err = v - _dot("shkv,shk->shv", held, k)
    held = held + (beta[..., None] * k)[..., None] * err[..., None, :]
    return _dot("shkv,shk->shv", held, q), held
