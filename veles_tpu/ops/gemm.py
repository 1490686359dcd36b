"""Matrix multiplication for the MXU, with reference precision levels.

Replaces the reference's hand-tuned OpenCL/CUDA GEMM family
(``ocl/matrix_multiplication_precise.cl``, ``ocl/gemm.cl``) and its
per-device block-size autotuner (``backends.py:623-731`` +
``devices/device_infos.json``). On TPU the design inverts: XLA's
``dot_general`` already emits the MXU schedule and fuses the bias and
activation epilogue itself, so that is the one path; there is no kernel
to choose here and nothing to tune (docs/performance.md "Why XLA's dot"
keeps the pre-round comparison that retired the blocked Pallas family).

Precision levels (reference ``config.py:244-247`` documented plain sum /
Kahan (+9%) / multi-partial (+90%) summation tiers):

- 0 → bfloat16 MXU passes, float32 accumulation (fast path),
- 1 → float32 operands, ``Precision.HIGH`` (≈ the Kahan tier),
- 2 → float32 operands, ``Precision.HIGHEST`` (≈ the multi-partial tier).
"""

import jax.numpy as jnp
from jax import lax

from veles_tpu.core.config import root

_PRECISIONS = {
    0: lax.Precision.DEFAULT,
    1: lax.Precision.HIGH,
    2: lax.Precision.HIGHEST,
}


def matmul(a, b, precision_level=None, out_dtype=None):
    """``a @ b`` on the MXU through ``lax.dot_general``.

    precision_level mirrors the reference's GEMM summation tiers (see
    module docstring); ``None`` reads
    ``root.common.engine.precision_level``."""
    if precision_level is None:
        precision_level = root.common.engine.get("precision_level", 0)
    if out_dtype is None:
        out_dtype = a.dtype
    (a, b), precision = compute_operands(
        a, b, precision_level=precision_level)
    return lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    ).astype(out_dtype)


def compute_operands(*arrays, precision_level=None):
    """Apply the engine compute-dtype policy to MXU operands: returns
    ``(cast_arrays, lax_precision)``. Level 0 casts to
    ``root.common.engine.compute_dtype`` (bf16 — halves the HBM bytes of
    every materialized operand feeding the MXU); levels 1/2 keep float32
    with HIGH/HIGHEST passes. The dense path (``matmul``/``dense_layer``)
    and the conv paths (``nn/conv.py``, ``parallel/fused.py``) all route
    through this one policy."""
    if precision_level is None:
        precision_level = root.common.engine.get("precision_level", 0)
    if precision_level == 0:
        compute_dtype = jnp.dtype(
            root.common.engine.get("compute_dtype", "bfloat16"))
    else:
        compute_dtype = jnp.float32
    return (tuple(a.astype(compute_dtype) for a in arrays),
            _PRECISIONS[precision_level])


def conv2d(x, w, sliding, padding, precision_level=None):
    """NHWC x HWIO convolution under the engine precision policy, f32
    result. Level 0 casts the operands to ``compute_dtype`` and runs the
    conv in that dtype end-to-end (the transpose rule under ``jax.vjp``
    requires uniform operand dtypes, so a mixed bf16-operand /
    f32-accumulator conv is not reverse-differentiable — the MXU still
    accumulates f32 internally; only the materialized output rounds
    through bf16), then casts the result back to f32 for the bias +
    activation epilogue. Levels 1/2 keep f32 operands with HIGH/HIGHEST
    passes and a f32 accumulator type. Both the graph conv unit
    (``nn/conv.py``) and the fused engine (``parallel/fused.py``) call
    THIS function, so the two modes stay bit-identical."""
    if precision_level is None:
        precision_level = root.common.engine.get("precision_level", 0)
    (xc, wc), precision = compute_operands(
        x, w, precision_level=precision_level)
    kwargs = {}
    if precision_level != 0:
        kwargs["preferred_element_type"] = jnp.float32
    out = lax.conv_general_dilated(
        xc, wc, window_strides=tuple(sliding), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision, **kwargs)
    return out.astype(jnp.float32)


def dense_layer(x, w, bias, activation="linear", precision_level=None,
                out_dtype=jnp.float32):
    """The product dense-layer forward: ``act(x @ w + b)`` as XLA's dot
    with its own epilogue fusion; bias add + activation on the f32
    accumulator, ONE final cast to ``out_dtype``."""
    from veles_tpu.ops import activations as act_lib
    act = act_lib.ACTIVATIONS[activation][0]
    (xc, wc), precision = compute_operands(
        x, w, precision_level=precision_level)
    out = lax.dot_general(
        xc, wc, (((xc.ndim - 1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)
    return act(out + bias).astype(out_dtype)
