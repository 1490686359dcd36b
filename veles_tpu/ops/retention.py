"""Power retention of degree 2: attention whose whole past is a state.

Softmax attention's ``exp(q·k)`` replaced by ``(q·k)² / d`` (Buckman,
Gelada, Zhang, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239) factors through a finite feature map, ``φ(q)·φ(k) =
(q·k)²``, so with a learned decay ``g_t`` in (0, 1) a K/V head's past
is a fixed-size state and a normaliser,

    S_t = g_t S_{t-1} + φ(k_t) v_tᵀ / d       z_t = g_t z_{t-1} + φ(k_t) / d
    y_t = φ(q_t)ᵀ S_t / (φ(q_t)·z_t + ε)

the same numbers as the attention form ``a_{t,j} = (q_t·k_j)² / d ·
exp(Σ_{r=j+1..t} log g_r)``, ``y_t = Σ_j a_{t,j} v_j / (Σ_j a_{t,j} +
ε)``. Query head ``i`` of ``H`` reads K/V head ``i // (H // G)``.

**The feature map** (:func:`phi`). ``φ(u)`` needs one entry a pair
``a ≤ b`` of the ``d`` values, ``d(d+1)/2`` = 8,256 at ``d`` = 128.
This module holds ``(d/2 + 1)·d`` = 8,320, laid out so that the TPU's
lanes make them without a gather: row ``o`` of ``d/2 + 1`` is ``w_o ·
u ⊙ roll(u, -o)``, lane ``l`` the product ``u_l u_{(l+o) mod d}``.
Offsets ``o`` and ``d - o`` hold the same pairs, so rows ``1 .. d/2 -
1`` carry each pair of theirs once (weight ``√2``), row 0 the squares
(weight 1) and row ``d/2`` its pairs twice (weight 1 each): ``φ(q)·φ(k)
= Σ_o c_o`` over all ``d`` offsets ``= (q·k)²``. 0.8% more than the
triangle, whole lane tiles, and a roll a row.

**What a slot holds** a K/V head: ``S`` ``(d, D')`` float32, ``v``'s
index on sublanes and the features on lanes (``D' = features(d)``),
and ``z`` ``(D',)``; the kind (``parallel/blocks.Retention``) declares
them as the fixed state of a slot.

**The three programs.** :func:`prompt` is the chunked form over whole
right-padded sequences: inside a chunk of ``CHUNK`` positions the
attention form (a ``c × c`` matrix with cumulative log-gates, no
``φ``), across chunks the state. :func:`state_after` is the state
after each row's true length (padding adds nothing and decays
nothing). :func:`step` is the recurrence for one new position a slot:
each live slot's state goes through the chip once, read, decayed,
added to and contracted with the group's queries while it is there,
and written back. :func:`state_path` is the rule
(``ops/platform.py``'s convention) that picks the Pallas kernel
``retention_step`` for it on a TPU and ``jax.numpy`` elsewhere.

Only degree 2: another degree has another feature map and is refused
by name where a model declares it (``parallel/blocks.Retention``).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.platform import (VMEM_MIB, device_kind, on_tpu,
                                    pallas_interpret)

#: added to the normaliser
EPS = 1e-6

#: positions :func:`prompt` takes in the attention form at once; a
#: longer sequence goes chunk by chunk through the state. Swept on the
#: v5e at the serving cell's admission (4 rows of 1,024, 40 heads over
#: 8; PERF.md §6, PR 37): below 4,128 positions a pair costs fewer
#: operations as a score than a token costs against the state, and the
#: state's side has the feature rows of every query to write and read.
CHUNK = 1024

#: sublanes of ``S`` the kernel holds in registers at once (a strip
#: of ``v``'s index): 4 vregs a lane tile, and as many accumulators a
#: query head
_STRIP = 32
#: lane tiles the kernel's loops take a pass, at most (65 tiles at
#: ``d`` = 128 go in 13 passes of 5): Mosaic unrolls a loop whole or
#: not at all, so the passes are written out
_UNROLL = 5

def features(head_dim):
    """``D'``: entries of ``φ`` this module holds a head."""
    return (head_dim // 2 + 1) * head_dim


def _weights(head_dim):
    half = head_dim // 2
    weight = numpy.full((half + 1, 1), math.sqrt(2.0), numpy.float32)
    weight[0] = weight[half] = 1.0
    return weight


def phi(u):
    """``(..., d)`` -> ``(..., D')`` float32, ``d`` even: row ``o`` of
    ``d/2 + 1`` is ``w_o · u ⊙ roll(u, -o)`` (the module's text)."""
    d = u.shape[-1]
    if d % 2:
        raise ValueError("the feature map pairs offsets o and d - o: "
                         "head_dim %d is odd" % d)
    wide = u.astype(jnp.float32)
    rolled = jnp.stack([jnp.roll(wide, -o, -1)
                        for o in range(d // 2 + 1)], -2)
    return (rolled * wide[..., None, :] * _weights(d)).reshape(
        u.shape[:-1] + (-1,))


def _by_head(fn, *xs):
    """``fn`` over one K/V head at a time (the group axis, 2, of every
    ``(B, T, G, ...)`` in ``xs``, taken in turn): a head's scores and
    feature rows are an eighth of the layer's, which at the serving
    cell's admission is what fits beside the weights. Results come
    back with the heads leading."""
    return lax.map(lambda part: fn(*part),
                   tuple(jnp.moveaxis(x, 2, 0) for x in xs))


def _chunk_scores(q, k, log_g):
    """The attention form inside a chunk of one K/V head: ``(a, b)``
    with ``a`` (B, R, c, c) float32 the weights ``(q_i·k_j)² / d ·
    exp(b_i - b_j)`` for ``j ≤ i`` (0 above the diagonal) and ``b``
    (B, c) the cumulative log-gates from the chunk's start, each
    position's own included. ``q`` (B, c, R, d), ``k`` (B, c, d)."""
    c, d = q.shape[1], q.shape[-1]
    b = jnp.cumsum(log_g, axis=1)
    s = jnp.einsum("bird,bjd->brij", q, k,
                   preferred_element_type=jnp.float32)
    seen = jnp.tril(jnp.ones((c, c), bool))
    gap = jnp.where(seen, b[:, :, None] - b[:, None, :], 0.0)
    decay = jnp.where(seen, jnp.exp(gap), 0.0)
    return s * s * decay[:, None] * (1.0 / d), b


def _chunk_state(k, v, log_g, live=None):
    """What a chunk adds to one K/V head's state, and its decay over
    the chunk: ``(S (B, d, D'), z (B, D'), decay (B,))`` of ``k``,
    ``v`` (B, c, d) and ``log_g`` (B, c). ``live`` (B, c) marks a
    row's own positions: the others add nothing and decay nothing."""
    d = k.shape[-1]
    if live is not None:
        log_g = jnp.where(live, log_g, 0.0)
    b = jnp.cumsum(log_g, axis=1)
    # what is left of position j at the chunk's end
    weight = jnp.exp(b[:, -1:] - b)
    if live is not None:
        weight = jnp.where(live, weight, 0.0)
    pk = phi(k) * (weight[..., None] * (1.0 / d))
    add = jnp.einsum("bjv,bjD->bvD", v, pk.astype(v.dtype),
                     preferred_element_type=jnp.float32)
    return add, jnp.sum(pk, axis=1), jnp.exp(b[:, -1])


def _chunks(x, c):
    """``(B, T, ...)`` -> ``(T // c, B, c, ...)``."""
    return jnp.moveaxis(
        x.reshape((x.shape[0], -1, c) + x.shape[2:]), 1, 0)


def _padded(xs, c):
    """Each ``(B, T, ...)`` of ``xs`` padded to whole chunks of ``c``
    (with zeros: a log-gate of 0 decays nothing)."""
    pad = -xs[0].shape[1] % c
    if not pad:
        return xs
    return tuple(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                 for x in xs)


def _empty(batch, d):
    """A K/V head's state before any position: ``(S, z)``."""
    wide = features(d)
    return (jnp.zeros((batch, d, wide), jnp.float32),
            jnp.zeros((batch, wide), jnp.float32))


def _after(carry, k, v, log_g, live=None):
    """The state ``carry`` a chunk later."""
    held, norm = carry
    add, add_z, decay = _chunk_state(k, v, log_g, live)
    return decay[:, None, None] * held + add, decay[:, None] * norm + add_z


def _head_prompt(q, k, v, log_g, c):
    """:func:`prompt` of one K/V head: ``q`` (B, T, R, d), ``k``, ``v``
    (B, T, d), ``log_g`` (B, T), ``T`` whole chunks of ``c`` ->
    (B, T, R, d)."""
    def inside(qi, ki, vi, gi):
        a, b = _chunk_scores(qi, ki, gi)
        # (the CPU's runtime has no bfloat16 product whose float32
        # result comes out turned: heads first, then turned)
        num = jnp.moveaxis(jnp.einsum(
            "brij,bjv->briv", a.astype(vi.dtype), vi,
            preferred_element_type=jnp.float32), 1, 2)
        return num, jnp.moveaxis(jnp.sum(a, -1), 1, -1), b  # (B, c, R)

    def answer(num, den):
        return (num / (den[..., None] + EPS)).astype(v.dtype)

    if q.shape[1] == c:         # one chunk: no state at all
        num, den, _ = inside(q, k, v, log_g)
        return answer(num, den)

    def body(carry, xs):
        held, norm = carry
        qi, ki, vi, gi = xs
        num, den, b = inside(qi, ki, vi, gi)
        # the chunk's queries against the state it began with
        pq = phi(qi) * jnp.exp(b)[..., None, None]
        num = num + jnp.einsum("birD,bvD->birv", pq, held,
                               precision=lax.Precision.HIGHEST)
        den = den + jnp.einsum("birD,bD->bir", pq, norm,
                               precision=lax.Precision.HIGHEST)
        return _after(carry, ki, vi, gi), answer(num, den)

    _, y = lax.scan(body, _empty(q.shape[0], q.shape[-1]),
                    tuple(_chunks(x, c) for x in (q, k, v, log_g)))
    return jnp.moveaxis(y, 0, 1).reshape(q.shape)


def prompt(q, k, v, log_g):
    """Whole sequences: ``q`` (B, T, H, d), ``k``, ``v`` (B, T, G, d),
    ``log_g`` (B, T, G) float32 -> ``y`` (B, T, H·d) in ``v``'s type.
    Causal, so a row's padding (after its last position) changes
    nothing before it. Operands in their own type, products
    accumulated in float32, the weights, sums and state in float32."""
    batch, t, heads, d = q.shape
    groups = k.shape[2]
    c = min(CHUNK, t)
    q, k, v, log_g = _padded((q, k, v, log_g), c)
    q = q.reshape(q.shape[:2] + (groups, -1, d))    # (B, T, G, R, d)
    y = _by_head(functools.partial(_head_prompt, c=c), q, k, v, log_g)
    return jnp.moveaxis(y, 0, 2).reshape(batch, -1, heads * d)[:, :t]


def _head_state(k, v, log_g, live, c):
    """:func:`state_after` of one K/V head: ``(S (B, d, D'), z (B,
    D'))``."""
    if k.shape[1] == c:
        return _chunk_state(k, v, log_g, live)[:2]
    return lax.scan(
        lambda carry, xs: (_after(carry, *xs), None),
        _empty(k.shape[0], k.shape[-1]),
        tuple(_chunks(x, c) for x in (k, v, log_g, live)))[0]


def state_after(k, v, log_g, live=None):
    """The state after each row's TRUE length: ``k``, ``v`` (B, T, G,
    d), ``log_g`` (B, T, G), ``live`` (B, T) bool marking a
    right-padded row's own positions (None: all of them) ->
    ``{"S": (B, G, d, D') float32, "z": (B, G, D') float32}``. A
    padded position adds nothing and decays nothing, so the bucket's
    end reads the same as the row's."""
    batch, t, groups, _ = k.shape
    c = min(CHUNK, t)
    if live is None:
        live = jnp.ones((batch, t), bool)
    live = jnp.broadcast_to(live[..., None], (batch, t, groups))
    held, norm = _by_head(functools.partial(_head_state, c=c),
                          *_padded((k, v, log_g, live), c))
    return {"S": jnp.moveaxis(held, 0, 1), "z": jnp.moveaxis(norm, 0, 1)}


# -- the decode step -----------------------------------------------------------

def vmem_claim():
    """VMEM the kernel's call claims, in bytes, or None where the
    chip's is not known: half the chip's (64 of a v5e's 128 MiB). Two
    buffers of a K/V head's ``S`` in and two out are 17 MB at ``d`` =
    128."""
    mib = VMEM_MIB.get(device_kind())
    return mib and (mib << 20) // 2


def state_path(leaf, sharding):
    """``"kernel"`` or ``"xla"``: how a decode step takes the state
    leaf ``leaf`` (``S`` of one block: an array, a tracer or a shape,
    ``(slots, G, d, D')``), which lies as ``sharding`` says (None:
    nobody knows), through the chip. The kernel on a TPU whose VMEM is
    known and holds four buffers of a head's state twice over, for a
    float32 state whose ``d`` is whole lane tiles and whole strips,
    known to lie on ONE device (a bare ``pallas_call`` cannot be
    partitioned); everything else keeps ``jax.numpy``. Read when a
    step program is traced and by the decoder for its books, with the
    same two arguments: no flag, key or option chooses."""
    if not on_tpu() or sharding is None or len(sharding.device_set) != 1 \
            or leaf.ndim != 4 or leaf.dtype != jnp.float32:
        return "xla"
    _, _, d, wide = leaf.shape
    claim = vmem_claim()
    if d % 128 or d % _STRIP or wide != features(d) or claim is None \
            or 2 * 4 * d * wide * 4 > claim:
        return "xla"
    return "kernel"


def _step_kernel(small_ref, s_ref, z_ref, out_ref, zout_ref, y_ref,
                 phi_ref, down_ref, acc_ref, *, rows):
    """One K/V head of one slot, its state through the chip once.
    ``small_ref`` (rows + 3, d) float32: the group's ``rows`` queries,
    then ``k / sqrt(d)`` (zeros for an idle lane: ``φ`` is quadratic,
    so its features are ``φ(k) / d``), ``v``, and the decay on every
    lane. ``s_ref``/``out_ref`` (d, D') and ``z_ref``/``zout_ref`` (1,
    D') are the state, read and written where it lies; ``y_ref`` (rows,
    d) the answers ``φ(q)ᵀS / (φ(q)·z + ε)`` against the NEW state.

    First the feature rows, made here from lane rotations (``φ``'s row
    ``o`` is ``w_o · u ⊙ roll(u, -o)``) on eight equal sublanes into
    ``phi_ref`` (rows + 1, 8, D'), so that the loop below multiplies
    whole registers and broadcasts nothing. Then ``S <- g·S + v φ(k)ᵀ``
    a lane tile and ``_STRIP`` sublanes at a time, each new register
    multiplied into the ``rows`` feature rows while it is there and
    summed over the tiles (``acc_ref`` (rows, d, d)); ``z`` alike on
    one sublane; last, each sum's lanes are added up and divided."""
    d, wide = s_ref.shape[-2:]
    tiles = wide // d
    unroll = max(n for n in range(1, _UNROLL + 1) if tiles % n == 0)

    def passes(one, start):
        """``one(tile index, carry)`` over every lane tile, ``unroll``
        of them written out a pass."""
        def some(i, carry):
            for n in range(unroll):
                carry = one(i * unroll + n, carry)
            return carry

        return lax.fori_loop(0, tiles // unroll, some, start)

    small = small_ref[0, 0]
    for r in range(rows + 1):
        same = jnp.broadcast_to(small[r:r + 1], (8, d))
        for o in range(tiles):
            feat = same * pltpu.roll(same, (d - o) % d, 1)
            if o not in (0, d // 2):
                feat = feat * math.sqrt(2.0)
            phi_ref[r, :, o * d:(o + 1) * d] = feat
    decay = jnp.broadcast_to(small[rows + 2:rows + 3], (8, d))
    # v down the sublanes, the same on every lane
    down_ref[...] = jnp.transpose(
        jnp.broadcast_to(small[rows + 1:rows + 2], (d, d)))
    parts = _STRIP // 8

    for strip in range(d // _STRIP):
        base = strip * _STRIP
        v_down = [down_ref[base + 8 * j:base + 8 * (j + 1), :]
                  for j in range(parts)]

        def tile(c, accs, base=base, v_down=v_down):
            lanes = pl.ds(pl.multiple_of(c * d, d), d)
            key = phi_ref[rows, :, lanes]
            queries = [phi_ref[r, :, lanes] for r in range(rows)]
            out = []
            for j in range(parts):
                at = slice(base + 8 * j, base + 8 * (j + 1))
                new = decay * s_ref[0, 0, at, lanes] + v_down[j] * key
                out_ref[0, 0, at, lanes] = new
                out.append(tuple(acc + new * query for acc, query
                                 in zip(accs[j], queries)))
            return tuple(out)

        accs = passes(tile, tuple(
            tuple(jnp.zeros((8, d), jnp.float32) for _ in range(rows))
            for _ in range(parts)))
        for j in range(parts):
            for r in range(rows):
                acc_ref[r, base + 8 * j:base + 8 * (j + 1), :] = accs[j][r]

    def norm_tile(c, accs):
        lanes = pl.ds(pl.multiple_of(c * d, d), d)
        new = decay[:1] * z_ref[0, 0, :, lanes] \
            + phi_ref[rows, 0:1, lanes]
        zout_ref[0, 0, :, lanes] = new
        return tuple(acc + new * phi_ref[r, 0:1, lanes]
                     for r, acc in enumerate(accs))

    dens = passes(norm_tile, tuple(
        jnp.zeros((1, d), jnp.float32) for _ in range(rows)))
    for r in range(rows):
        # (d, lanes) -> lanes summed, as a row: turned, then down
        num = jnp.sum(jnp.transpose(acc_ref[r]), axis=0, keepdims=True)
        den = jnp.sum(dens[r], axis=1, keepdims=True)
        y_ref[0, 0, r:r + 1, :] = num / (den + EPS)


@functools.partial(jax.jit, static_argnames=("interpret", "claim"))
def _step_call(small, held, norm, interpret, claim):
    """The ``pallas_call``, a function jitted on its own so that a
    program with a block of this kind in every layer lowers the kernel
    once and calls it (``ops/slab_attention._walk`` has the cost of
    not doing so). ``held``'s and ``norm``'s buffers are the
    results': a caller that lets go of them (the chunk's carry) has
    them updated where they lie. ``small`` (S, G, rows + 3, d) as
    :func:`_step_kernel` reads it."""
    slots, groups, d, wide = held.shape
    rows = small.shape[2] - 3

    def head(*block):
        return pl.BlockSpec((1, 1) + block, lambda s, g: (s, g, 0, 0))

    call = pl.pallas_call(
        functools.partial(_step_kernel, rows=rows),
        grid=(slots, groups),
        in_specs=[head(rows + 3, d), head(d, wide), head(1, wide)],
        out_specs=[head(d, wide), head(1, wide), head(rows, d)],
        out_shape=[jax.ShapeDtypeStruct(held.shape, held.dtype),
                   jax.ShapeDtypeStruct((slots, groups, 1, wide),
                                        norm.dtype),
                   jax.ShapeDtypeStruct((slots, groups, rows, d),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows + 1, 8, wide), jnp.float32),
                        pltpu.VMEM((d, d), jnp.float32),
                        pltpu.VMEM((rows, d, d), jnp.float32)],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=claim),
        name="retention_step",
        interpret=interpret)
    # the scope again, inside the jit: a reader of the scope table
    # knows an op by the innermost two names of its op_name, and
    # ``jit(_step_call)`` would be one of them
    with jax.named_scope("ret.state"):
        return call(small, held, norm.reshape(slots, groups, 1, wide))


def step(q, k, v, log_g, held, norm, active, sharding=None):
    """One new position a slot: ``q`` (S, H, d), ``k``, ``v`` (S, G,
    d), ``log_g`` (S, G) float32, the slots' state ``held`` (S, G, d,
    D') and ``norm`` (S, G, D'), ``active`` (S,) bool -> ``(y (S, H·d)
    float32, held, norm)``. A lane that is not ``active`` keeps its
    state as it was (decay 1, nothing added) and its answer is no
    one's. ``sharding`` is where ``held`` lies, for the rule."""
    slots, groups, d = k.shape
    decay = jnp.where(active[:, None], jnp.exp(log_g), 1.0)
    if state_path(held, sharding) == "kernel":
        with jax.named_scope("ret.state"):
            # the kernel makes the feature rows itself: it is handed
            # the vectors, a K/V head's in one block
            scale = jnp.where(active, 1.0 / math.sqrt(d), 0.0)
            small = jnp.concatenate([
                q.reshape(slots, groups, -1, d).astype(jnp.float32),
                (k.astype(jnp.float32) * scale[:, None, None])[:, :, None],
                v.astype(jnp.float32)[:, :, None],
                jnp.broadcast_to(decay[..., None, None],
                                 (slots, groups, 1, d))], axis=2)
            held, norm, y = _step_call(
                small, held, norm, interpret=pallas_interpret(),
                claim=vmem_claim())
            return y.reshape(slots, -1), held, norm[:, :, 0]
    with jax.named_scope("ret.phi"):
        pq = phi(q.reshape(slots, groups, -1, d))           # (S, G, R, D')
        pk = phi(k) * jnp.where(active, 1.0 / d, 0.0)[:, None, None]
    with jax.named_scope("ret.state"):
        norm = decay[..., None] * norm + pk
        den = jnp.einsum("sgrD,sgD->sgr", pq, norm,
                         precision=lax.Precision.HIGHEST)
        held = decay[..., None, None] * held \
            + v.astype(jnp.float32)[..., :, None] * pk[..., None, :]
        num = jnp.einsum("sgrD,sgvD->sgrv", pq, held,
                         precision=lax.Precision.HIGHEST)
        y = num / (den[..., None] + EPS)
    return y.reshape(slots, -1), held, norm
