"""Int8 weight-only quantization with a dequant-fused Pallas matvec.

The serving decode loop is memory-bound: every token reads every weight
matrix out of HBM (``parallel/decode.py``; the bf16 tier already bought
+~50% tokens/sec by halving that traffic). This module halves it AGAIN:
weights live in HBM as int8 with one f32 scale per output channel, and
the Pallas kernel dequantizes inside the matvec — the bf16/f32 weights
never exist in HBM at all.

Measured on TPU v5e before this round (two-length scan timing, m=8
decode rows; no ledger row yet): the kernel beats XLA's fused-convert
dot 6x on the qkv projection shape (k1024 x n3072 — XLA handles the
non-power-of-two N badly) and ~1.3x on the 32k vocab head, and ties
within noise on the square shapes — WHEN the in-kernel dequant matches
the activation dtype (bf16 serving) and the lane block suits the
shape. ``use_int8_kernel`` is the rule read off that sweep: the
platform and the static shapes decide, nothing else (the
flash-attention >=4096 doctrine).

Quantization scheme: symmetric per-output-channel absmax
(``q = round(w / scale)`` with ``scale = absmax / 127``), the standard
W8A16 serving recipe — activations stay bf16/f32, so the only numeric
change is the weight rounding (|error| <= scale/2 per element,
``tests/test_quant.py``).

No reference counterpart: VELES ships fp16 export precision at most
(``workflow.py:864-971``); this is an additive serving tier.
"""

import functools

import jax
import jax.numpy as jnp

from veles_tpu.ops.platform import on_tpu

#: the Pallas path auto-engages below this many rows of x: the decode
#: regime (M = batch) where the matvec is HBM-bound and the x block
#: (M x K) stays a sliver of VMEM. Above it (prefill, training) the
#: MXU-bound XLA dequant path wins and engages instead.
PALLAS_MAX_ROWS = 256

#: lane-block candidates per grid step (N must divide by the choice)
BLOCK_N_CANDIDATES = (2048, 1024, 512)

def quantize_int8(w):
    """Symmetric per-output-channel int8 quantization of ``w`` (K, N):
    returns ``(q int8 (K, N), scale f32 (N,))`` with
    ``w ~= q * scale``. Zero columns get scale 1 (q = 0)."""
    w = jnp.asarray(w)
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def _matvec_kernel(x_ref, q_ref, s_ref, o_ref):
    # x (M, K) | q (K, BN) int8 | s (1, BN) f32 -> o (M, BN) f32.
    # The int8 block widens to x's dtype in VMEM only (HBM saw one byte
    # per weight); the MXU accumulates in f32 either way. bf16 x keeps
    # the MXU on its native input width — measured faster than f32 at
    # every shape that matters (see module docstring).
    w = q_ref[:].astype(x_ref.dtype)
    o_ref[:] = jnp.dot(x_ref[:], w,
                       preferred_element_type=jnp.float32) * s_ref[:]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _pallas_int8_matmul(x, q, scale, block_n, interpret=False):
    from jax.experimental import pallas as pl

    m, k = x.shape
    n = q.shape[1]
    return pl.pallas_call(
        _matvec_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((m, k), lambda j: (0, 0)),
            pl.BlockSpec((k, block_n), lambda j: (0, j)),
            pl.BlockSpec((1, block_n), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, block_n), lambda j: (0, j)),
        interpret=interpret,
    )(x, q, scale.reshape(1, -1))


def _default_block_n(k, n):
    """Lane block for the shape. From the v5e sweep: the 32k vocab
    head wants 2048; mid-width projections want 1024; 512 is the floor
    that still always fits VMEM."""
    for candidate in BLOCK_N_CANDIDATES:
        if n % candidate == 0 and (candidate < 2048 or n >= 16384):
            return candidate
    return 512 if n % 512 == 0 else None


def use_int8_kernel(m, k, n):
    """Whether ``int8_matmul`` of (m, k) x (k, n) takes the Pallas
    kernel: on the TPU, in the decode regime (a prefill/training call,
    m up to B x T, would blow the kernel's whole-x VMEM block and is
    MXU-bound, where XLA wins), at shapes the kernel tiles."""
    return (on_tpu() and m <= PALLAS_MAX_ROWS and k % 32 == 0
            and _default_block_n(k, n) is not None)


def int8_matmul(x, q, scale, use_pallas=None, interpret=False):
    """``x @ (q * scale)`` with the dequantization fused into the
    product. ``x`` (M, K) float; ``q`` (K, N) int8; ``scale`` (N,) f32.
    Returns (M, N) in ``x``'s dtype.

    ``use_pallas=None`` asks ``use_int8_kernel``; True/False are how
    the tests compare the kernel with the XLA formulation
    (dequant-to-x.dtype feeding dot_general)."""
    m, k = x.shape
    n = q.shape[1]
    if use_pallas is None:
        use_pallas = use_int8_kernel(m, k, n)
    if use_pallas:
        block_n = _default_block_n(k, n)
        if block_n is None or k % 32:
            # only an EXPLICIT request reaches here (the rule already
            # checked both): running the XLA product under a forced-on
            # flag would measure XLA against XLA
            raise ValueError(
                "int8_matmul: use_pallas=True cannot be honoured for "
                "k=%d, n=%d (the kernel needs k %% 32 == 0 and "
                "n %% 512 == 0); drop the force or pad the shape"
                % (k, n))
        out = _pallas_int8_matmul(x, q, scale, block_n,
                                  interpret=interpret)
        return out.astype(x.dtype)
    compute = x.dtype if x.dtype != jnp.float64 else jnp.float32
    out = jnp.dot(x, q.astype(compute),
                  preferred_element_type=jnp.float32)
    return (out * scale).astype(x.dtype)


def int8_cache_attend(q, k_q, k_scale, v_q, v_scale, mask_addend,
                      tail=None):
    """Decode attention of one query token against an int8 KV cache in
    the head-major (B, H, D, T) layout, dequantization fused into the
    dots. ``q`` (B, 1, H, D) float (already 1/sqrt(D)-scaled by the
    caller); per-(position, head) ``k_scale``/``v_scale`` (B, H, T)
    f32; ``mask_addend`` f32 (0 = visible, -1e30 = masked) — shape
    (T,) for one shared mask, or (B, T) for per-row masks (the slot
    engine's per-slot lengths). ``tail`` is ``(k_q, k_scale, v_q,
    v_scale, mask_addend)`` of more positions that lie in another
    buffer (the slot chunk's staged columns): one softmax over both,
    no copy that joins them. Returns (B, 1, H, D) f32.

    One formulation, XLA's: on THIS head-major layout XLA keeps the
    int8 payloads narrow all the way into the dots (the positions-major
    layouts were what forced the materialized bf16 widening). The
    per-batch Pallas twin that used to sit beside it lost to this on
    the pre-round record (speedup 0.977) and its
    per-row ``(1, T)`` mask block broke Mosaic's (8, 128) rule, so it
    was deleted rather than left forceable; the paged engine's fused
    kernel is ``ops/paged_attention.paged_attend_int8``."""
    compute = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    qh = q[:, 0].astype(compute)                        # (B,H,D)
    parts = [(k_q, k_scale, v_q, v_scale, mask_addend)] \
        + ([tail] if tail is not None else [])
    scores = []
    for kq, ks, _, _, addend in parts:
        s = jnp.einsum("bhd,bhdt->bht", qh, kq.astype(compute),
                       preferred_element_type=jnp.float32)
        scores.append(s * ks + (addend if addend.ndim == 1
                                else addend[:, None, :]))  # (B,1,T)
    p = jax.nn.softmax(jnp.concatenate(scores, axis=-1)
                       if tail is not None else scores[0], axis=-1)
    out, at = None, 0
    for (_, _, vq, vs, _), s in zip(parts, scores):
        pv = (p[..., at:at + s.shape[-1]] * vs).astype(compute)
        part = jnp.einsum("bhdt,bht->bhd", vq.astype(compute), pv,
                          preferred_element_type=jnp.float32)
        out = part if out is None else out + part
        at += s.shape[-1]
    return out[:, None]


def matmul_any(x, w):
    """``x @ w`` where ``w`` is a dense array OR the quantized
    ``{"q8", "scale"}`` dict — the single dispatch point the shared
    transformer sublayer math routes through, so one code path serves
    the fp32, bf16 and int8 tiers (leading dims of ``x`` are
    flattened for the product)."""
    if isinstance(w, dict):
        lead = x.shape[:-1]
        y = int8_matmul(x.reshape(-1, x.shape[-1]), w["q8"], w["scale"])
        return y.reshape(lead + (w["q8"].shape[1],))
    return x @ w
