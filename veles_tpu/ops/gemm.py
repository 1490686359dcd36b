"""Matrix multiplication for the MXU, with reference precision levels.

Replaces the reference's hand-tuned OpenCL/CUDA GEMM family
(``ocl/matrix_multiplication_precise.cl``, ``ocl/gemm.cl``) and its
per-device block-size autotuner (``backends.py:623-731`` +
``devices/device_infos.json``). On TPU the design inverts: XLA's
``dot_general`` already emits optimal MXU schedules for standard shapes, so
that is the default path; the Pallas kernel below exists for the fused /
blocked cases XLA can't express (and as the substrate for later fused
epilogues), with a tiny autotune cache mirroring ``device_infos.json``.

Precision levels (reference ``config.py:244-247`` documented plain sum /
Kahan (+9%) / multi-partial (+90%) summation tiers):

- 0 → bfloat16 MXU passes, float32 accumulation (fast path),
- 1 → float32 operands, ``Precision.HIGH`` (≈ the Kahan tier),
- 2 → float32 operands, ``Precision.HIGHEST`` (≈ the multi-partial tier).
"""

import functools
import json
import logging
import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.core.config import root
from veles_tpu.observe.xla_stats import instrument
from veles_tpu.ops.platform import on_tpu

_PRECISIONS = {
    0: lax.Precision.DEFAULT,
    1: lax.Precision.HIGH,
    2: lax.Precision.HIGHEST,
}


def matmul(a, b, precision_level=None, out_dtype=None, use_pallas=None):
    """``a @ b`` tuned for the MXU.

    precision_level mirrors the reference's GEMM summation tiers (see
    module docstring); ``None`` reads
    ``root.common.engine.precision_level``.

    ``use_pallas``: True/False force the path; None reads
    ``root.common.engine.use_pallas``, whose default ``"tuned"`` engages
    the Pallas blocked kernel exactly where a persisted autotune verdict
    says it MEASURED faster than XLA on this device (``autotune_matmul``
    stores ``beats_xla`` per shape bucket — the reference's per-device
    GEMM autotune semantics, ``backends.py:623-731``: tuned result used
    automatically, XLA otherwise)."""
    if precision_level is None:
        precision_level = root.common.engine.get("precision_level", 0)
    if out_dtype is None:
        out_dtype = a.dtype
    if use_pallas is None:
        use_pallas = root.common.engine.get("use_pallas", "tuned")
    (a, b), precision = compute_operands(
        a, b, precision_level=precision_level)
    if use_pallas and _pallas_eligible(a, b):
        if use_pallas != "tuned" or _tuned_beats_xla(a, b):
            return pallas_matmul(a, b, out_dtype=out_dtype)
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


def _pallas_eligible(a, b):
    """Pallas pays off for large 2-D matmuls on a real TPU backend; small or
    ragged shapes go to XLA which handles padding better."""
    if a.ndim != 2 or b.ndim != 2:
        return False
    if not on_tpu():
        return False
    m, k = a.shape
    _, n = b.shape
    return m >= 512 and n >= 512 and k >= 512


# -- Pallas blocked matmul ---------------------------------------------------

def _mm_kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 operands need HIGHEST or the dot truncates to bf16 passes; bf16
    # operands must keep DEFAULT (Mosaic rejects fp32 contract precision on
    # a bf16 lhs) and already accumulate in f32 on the MXU
    precision = (lax.Precision.HIGHEST if a_ref.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=precision)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("out_dtype", "bm", "bn", "bk",
                                    "interpret"))
def pallas_matmul(a, b, out_dtype=jnp.float32, bm=None, bn=None, bk=None,
                  interpret=False):
    """Blocked MXU matmul: grid (M/bm, N/bn, K/bk), float32 VMEM accumulator,
    K innermost so each (i, j) output tile is revisited sequentially
    (``dimension_semantics``: parallel, parallel, arbitrary)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if bm is None or bn is None or bk is None:
        bm, bn, bk = _tuned_blocks(m, n, k, str(jnp.dtype(a.dtype)))
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    # pad to block multiples; zero padding is sum-neutral
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    mm, nn, kk = m + pm, n + pn, k + pk
    out = pl.pallas_call(
        _mm_kernel,
        grid=(mm // bm, nn // bn, kk // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    if pm or pn:
        out = out[:m, :n]
    return out


# compile/hit telemetry for the blocked kernel (observe/xla_stats.py);
# delegates after one attribute check while device telemetry is off
pallas_matmul = instrument("gemm.pallas_matmul", pallas_matmul)


# -- fused dense epilogue -----------------------------------------------------

def _mm_epilogue_kernel(activation):
    from veles_tpu.ops import activations as act_lib
    act = act_lib.ACTIVATIONS[activation][0]

    def kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        precision = (lax.Precision.HIGHEST
                     if a_ref.dtype == jnp.float32
                     else lax.Precision.DEFAULT)
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32,
                                precision=precision)

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _flush():
            # THE epilogue: bias add + activation on the f32 VMEM
            # accumulator tile, before it ever leaves for HBM
            o_ref[...] = act(acc_ref[...]
                             + bias_ref[...]).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("activation", "out_dtype", "bm",
                                    "bn", "bk", "interpret"))
def pallas_dense(a, b, bias, activation="linear", out_dtype=jnp.float32,
                 bm=None, bn=None, bk=None, interpret=False):
    """act(a @ b + bias) as ONE blocked kernel (matmul + epilogue)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    if bm is None or bn is None or bk is None:
        bm, bn, bk = _tuned_blocks(m, n, k, str(jnp.dtype(a.dtype)))
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    bias2 = bias.reshape(1, -1).astype(jnp.float32)
    if pn:
        bias2 = jnp.pad(bias2, ((0, 0), (0, pn)))
    mm, nn, kk = m + pm, n + pn, k + pk
    out = pl.pallas_call(
        _mm_epilogue_kernel(activation),
        grid=(mm // bm, nn // bn, kk // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((1, bn), lambda i, j, s: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mm, nn), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, bias2)
    if pm or pn:
        out = out[:m, :n]
    return out


pallas_dense = instrument("gemm.pallas_dense", pallas_dense)


@functools.lru_cache(maxsize=None)
def _dense_with_vjp(activation):
    """The Pallas epilogue forward with a hand-written VJP —
    ``pallas_call`` has no automatic reverse rule, and the fused tick
    differentiates straight through the layer. The backward is the
    SAME math the graph-mode GD units run (activation derivative off
    the saved OUTPUT, two transposed matmuls, bias row-sum) — with one
    caveat: ``grad_w`` accumulates in f32 and is then cast to
    ``w.dtype`` (bf16 on the Pallas path), one extra bf16 rounding of
    the weight gradient that graph-mode GD (f32 matmul output) does not
    apply. CPU tests can't observe it (``_pallas_eligible`` is false
    off-TPU); on TPU the fused-vs-graph weight comparison needs the
    looser TPU-tier bound."""
    from veles_tpu.ops import activations as act_lib
    deriv = act_lib.ACTIVATIONS[activation][1]

    @jax.custom_vjp
    def fn(x, w, b):
        return pallas_dense(x, w, b, activation=activation,
                            out_dtype=jnp.float32)

    def fwd(x, w, b):
        y = fn(x, w, b)
        return y, (x, w, y)

    def bwd(res, g):
        x, w, y = res
        err = g * deriv(y)
        grad_x = matmul(err, w.T, out_dtype=x.dtype)
        grad_w = matmul(x.T, err, out_dtype=jnp.float32).astype(w.dtype)
        return grad_x, grad_w, jnp.sum(err, axis=0)

    fn.defvjp(fwd, bwd)
    return fn


def dense_layer(x, w, bias, activation="linear", precision_level=None,
                out_dtype=jnp.float32, use_pallas=None):
    """The product dense-layer forward: ``act(x @ w + b)``.

    Default path: XLA dot + its own epilogue fusion — MEASURED faster
    than the Pallas kernels on the train composite (fwd+bwd+update,
    mb 4096: 0.40 vs 0.73 ms/step; docs/performance.md "Pallas +
    autotune" has the full table). Opt in to the fused Pallas epilogue
    kernel (``root.common.engine.use_pallas`` + ``pallas_epilogue``,
    or ``use_pallas=True`` here) for the shapes where it wins —
    forward-only tall-skinny (m=512, n=k=4096 measured 2.6x faster
    than XLA) — with the autotune cache's block sizes applied (the
    role the reference's per-device GEMM autotune played for every
    All2All, ``backends.py:623-731``)."""
    if use_pallas is None:
        use_pallas = root.common.engine.get("use_pallas", False) \
            and root.common.engine.get("pallas_epilogue", False)
    (xc, wc), precision = compute_operands(
        x, w, precision_level=precision_level)
    if use_pallas and _pallas_eligible(xc, wc):
        return _dense_with_vjp(activation)(xc, wc, bias).astype(
            out_dtype)
    from veles_tpu.ops import activations as act_lib
    act = act_lib.ACTIVATIONS[activation][0]
    # same dtype contract as the Pallas path: bias add + activation on
    # the f32 accumulator, ONE final cast to out_dtype
    out = lax.dot_general(
        xc, wc, (((xc.ndim - 1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)
    return act(out + bias).astype(out_dtype)


# -- autotune cache (the device_infos.json descendant) ------------------------

_DEFAULT_BLOCKS = (256, 256, 512)
_CANDIDATES = ((128, 128, 512), (256, 256, 512), (512, 512, 512),
               (256, 512, 512), (512, 256, 512), (256, 256, 1024))
_tuning_cache = None


def _cache_path():
    return root.common.engine.get(
        "pallas_autotune_cache",
        os.path.expanduser("~/.veles_tpu/cache/pallas_tuning.json"))


#: the timing fields every autotune entry may carry; all must be
#: positive finite seconds — a negative "measurement" is the two-length
#: slope estimator going underwater on timing jitter, not physics
_TIMING_KEYS = ("seconds", "xla_seconds")
_insane_warned = False


def _sane_entry(entry):
    """True when an autotune row is physically possible: a dict whose
    timing fields (if present) are positive finite numbers. A
    persisted NEGATIVE xla_seconds once gated a product matmul on a
    measurement that never happened."""
    if not isinstance(entry, dict):
        return False
    for key in _TIMING_KEYS:
        if key in entry:
            value = entry[key]
            if isinstance(value, bool) \
                    or not isinstance(value, (int, float)) \
                    or not math.isfinite(value) or value <= 0:
                return False
    return True


def _drop_insane(cache, where):
    """Remove physically impossible rows in place (warn once); the
    dropped bucket simply re-tunes on its next autotune run — default
    blocks and the XLA path serve it meanwhile."""
    global _insane_warned
    bad = [key for key, entry in cache.items()
           if not _sane_entry(entry)]
    for key in bad:
        del cache[key]
    if bad and not _insane_warned:
        _insane_warned = True
        logging.getLogger("gemm.autotune").warning(
            "dropped %d physically impossible autotune entr%s %s "
            "(non-positive or non-finite timing — the slope estimator "
            "went underwater on jitter): %s; affected buckets re-tune "
            "on next use (reported once)",
            len(bad), "y" if len(bad) == 1 else "ies", where,
            ", ".join(sorted(bad)))
    return bad


def _load_cache():
    global _tuning_cache
    if _tuning_cache is None:
        try:
            with open(_cache_path(), "r") as fin:
                _tuning_cache = json.load(fin)
        except (OSError, ValueError):
            _tuning_cache = {}
        if not isinstance(_tuning_cache, dict):
            _tuning_cache = {}
        # hygiene at load: poisoned rows from older rounds are dropped
        # AND the cleaned cache is persisted back so the artifact on
        # disk stops advertising the impossible measurement
        if _drop_insane(_tuning_cache, "at load"):
            _persist_cache(_tuning_cache)
    return _tuning_cache


def _persist_cache(cache):
    """Write the (already-updated) tuning cache to disk; shared by the
    GEMM and int8-matvec autotuners. Insane rows (non-positive /
    non-finite timings) are rejected here too, so no caller can
    re-poison the artifact."""
    global _tuning_cache
    _drop_insane(cache, "at persist")
    _tuning_cache = cache
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fout:
            json.dump(cache, fout, indent=1)
    except OSError:
        pass


def _tuned_blocks(m, n, k, dtype):
    key = "%s:%d" % (dtype, _size_bucket(m, n, k))
    entry = _load_cache().get(key)
    if entry:
        return tuple(entry["blocks"])
    return _DEFAULT_BLOCKS


def _tuned_beats_xla(a, b):
    """The "tuned" gate: engage Pallas only where an autotune run on
    this device recorded the kernel beating XLA for the shape bucket
    (absent/old entries without the verdict stay on XLA)."""
    m, k = a.shape
    n = b.shape[1]
    key = "%s:%d" % (str(jnp.dtype(a.dtype)), _size_bucket(m, n, k))
    entry = _load_cache().get(key)
    return bool(entry and entry.get("beats_xla"))


def _size_bucket(m, n, k):
    size = m * n * k
    bucket = 0
    while size > 1:
        size >>= 3  # buckets by order of magnitude in each dim
        bucket += 1
    return bucket


def autotune_main(argv=None):
    """``python -m veles_tpu autotune MxNxK[,MxNxK...]`` — benchmark the
    Pallas GEMM block candidates for each shape on the current device and
    persist the winners (the role of the reference's per-device GEMM
    autotune + ``devices/device_infos.json``)."""
    import argparse
    parser = argparse.ArgumentParser(prog="veles_tpu autotune")
    parser.add_argument("shapes",
                        help="comma-separated MxNxK matmul shapes")
    parser.add_argument("--dtype", default="bfloat16",
                        choices=("bfloat16", "float32"))
    parser.add_argument("--iters", type=int, default=3)
    parser.add_argument("--int8", action="store_true",
                        help="tune the int8 dequant-fused matvec "
                             "(ops/quant.py) instead of the GEMM: "
                             "shapes are MxKxN")
    args = parser.parse_args(argv)
    dtype = getattr(jnp, args.dtype)
    failed = 0
    if args.int8:
        from veles_tpu.ops.quant import autotune_int8
        for spec in args.shapes.split(","):
            m, k, n = (int(x) for x in spec.lower().split("x"))
            decision = autotune_int8(m, k, n, dtype=dtype)
            key = "int8:%dx%d" % (k, n)
            try:
                with open(_cache_path()) as fin:
                    persisted = key in json.load(fin)
            except (OSError, ValueError):
                persisted = False
            if not persisted:
                failed += 1
            print(json.dumps(dict(decision, shape=[m, k, n],
                                  persisted=persisted,
                                  cache=_cache_path())))
        return 1 if failed else 0
    for spec in args.shapes.split(","):
        m, n, k = (int(x) for x in spec.lower().split("x"))
        blocks = autotune_matmul(m, n, k, dtype=dtype, iters=args.iters)
        key = "%s:%d" % (str(jnp.dtype(dtype)), _size_bucket(m, n, k))
        try:  # read the file back: proves the winner actually persisted
            with open(_cache_path()) as fin:
                persisted = key in json.load(fin)
        except (OSError, ValueError):
            persisted = False
        if not persisted:
            failed += 1
        print(json.dumps({"shape": [m, n, k], "dtype": args.dtype,
                          "blocks": list(blocks),
                          "persisted": persisted,
                          "cache": _cache_path()}))
    # nonzero when nothing ran/persisted (e.g. no candidate fits or the
    # Pallas kernels are unavailable on this backend)
    return 1 if failed else 0


def log_candidate_failure(what, exc):
    """An autotune candidate that did not compile or run. On the TPU
    the compiler's message IS the finding (which block shape Mosaic
    refused, and why), so it is logged at error level per candidate;
    off the TPU every candidate fails for the one known reason (the
    kernels are not interpreted here) and a debug line is enough."""
    log = logging.getLogger("gemm.autotune")
    (log.error if on_tpu() else log.debug)(
        "autotune candidate %s failed: %s: %s", what,
        type(exc).__name__, exc)


def _matmul_scan_time(product, a, lengths=(50, 350), repeats=4):
    """Device sec/iter of ``product(a)`` via two-length serialized
    scans with a host-read fence: the difference of the two lengths
    cancels the per-call constants (dispatch, transfer, readback) on
    any host."""
    import time

    def loop(length):
        @jax.jit
        def run(a0):
            def body(carry, _):
                out = product(carry)
                # un-foldable epsilon dependence serializes iterations
                return carry + (jnp.sum(out) * 1e-38).astype(
                    carry.dtype), ()
            return jnp.sum(lax.scan(body, a0, None,
                                    length=length)[0])
        return run

    best = {}
    for length in lengths:
        run = loop(length)
        float(run(a))  # compile + warm
        t = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(run(a))
            t = min(t, time.perf_counter() - t0)
        best[length] = t
    return (best[lengths[1]] - best[lengths[0]]) \
        / (lengths[1] - lengths[0])


def autotune_matmul(m, n, k, dtype=jnp.bfloat16, iters=4):
    """Benchmark candidate block sizes AND the XLA dot for this shape
    bucket, persist the winner with a ``beats_xla`` verdict (reference
    ``backends.py:623-731`` per-device GEMM autotune — the tuned result
    then engages automatically through ``matmul``'s "tuned" gate).
    ``iters`` = timing repeats per measured scan length."""
    rng_a = jnp.ones((m, k), dtype) * 0.01
    b = jnp.ones((k, n), dtype) * 0.01

    best, best_dt = None, float("inf")
    for bm, bn, bk in _CANDIDATES:
        if bm > m or bn > n or bk > k:
            continue
        try:
            dt = _matmul_scan_time(
                lambda v, bm=bm, bn=bn, bk=bk: pallas_matmul(
                    v, b, out_dtype=jnp.float32, bm=bm, bn=bn,
                    bk=bk).astype(dtype), rng_a, repeats=iters)
        except Exception as exc:
            log_candidate_failure(
                "gemm %dx%dx%d blocks=%s" % (m, n, k, (bm, bn, bk)), exc)
            continue
        if dt < best_dt:
            best, best_dt = (bm, bn, bk), dt
    if best is None:
        # no viable candidate (e.g. off-TPU): skip the XLA baseline
        # too — there is nothing to compare it against
        return _DEFAULT_BLOCKS
    xla_dt = _matmul_scan_time(
        lambda v: lax.dot_general(
            v, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dtype), rng_a,
        repeats=iters)
    entry = {
        "blocks": list(best), "seconds": best_dt,
        "xla_seconds": xla_dt,
        # require a clear margin: a tie-level "win" (sub-noise) must
        # not flip a product matmul onto the kernel
        "beats_xla": best_dt < 0.97 * xla_dt}
    if not _sane_entry(entry):
        # the slope estimator went underwater (timing jitter can make
        # the long scan finish "faster" than the short one): a
        # physically impossible number must never be persisted as a
        # tuning verdict — keep the previous entry, re-tune later
        logging.getLogger("gemm.autotune").warning(
            "autotune %dx%dx%d measured an impossible timing "
            "(pallas %.3g s, xla %.3g s); verdict NOT persisted — "
            "re-run autotune for this shape", m, n, k, best_dt, xla_dt)
        return best
    cache = _load_cache()
    cache["%s:%d" % (str(jnp.dtype(dtype)),
                     _size_bucket(m, n, k))] = entry
    _persist_cache(cache)
    return best
