"""Int8 weight-only quantization tier (ops/quant.py + the decode
serving path): quantization error bounds, Pallas kernel == XLA
formulation, and end-to-end generate() wiring."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops.quant import (int8_matmul, matmul_any, quantize_int8)


def test_quantize_roundtrip_error_bound():
    """|w - q*scale| <= scale/2 per element (symmetric absmax)."""
    rng = numpy.random.RandomState(0)
    w = rng.randn(64, 128).astype(numpy.float32)
    q, scale = quantize_int8(w)
    assert q.dtype == jnp.int8 and scale.shape == (128,)
    err = numpy.abs(numpy.asarray(q, numpy.float32) *
                    numpy.asarray(scale) - w)
    assert (err <= numpy.asarray(scale) / 2 + 1e-7).all()
    # absmax elements hit +-127 exactly
    assert int(numpy.abs(numpy.asarray(q)).max()) == 127


def test_quantize_zero_column_safe():
    w = numpy.zeros((32, 128), numpy.float32)
    q, scale = quantize_int8(w)
    assert (numpy.asarray(q) == 0).all()
    assert (numpy.asarray(scale) == 1.0).all()


def test_xla_path_matches_manual_dequant():
    rng = numpy.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 64).astype(numpy.float32))
    w = rng.randn(64, 128).astype(numpy.float32)
    q, scale = quantize_int8(w)
    got = int8_matmul(x, q, scale, use_pallas=False)
    want = x @ (numpy.asarray(q, numpy.float32) * numpy.asarray(scale))
    numpy.testing.assert_allclose(numpy.asarray(got),
                                  numpy.asarray(want), rtol=2e-5,
                                  atol=1e-5)


def test_pallas_kernel_matches_xla_exactly_on_integers():
    """Integer x, scale folded to 1: both paths accumulate exact f32
    integers -> bitwise-equal results (pins the kernel's indexing)."""
    rng = numpy.random.RandomState(2)
    x = jnp.asarray(rng.randint(-8, 8, (8, 64)).astype(numpy.float32))
    q = jnp.asarray(rng.randint(-127, 127, (64, 512)), jnp.int8)
    scale = jnp.ones(512, jnp.float32)
    got = int8_matmul(x, q, scale, use_pallas=True, interpret=True)
    want = int8_matmul(x, q, scale, use_pallas=False)
    numpy.testing.assert_array_equal(numpy.asarray(got),
                                     numpy.asarray(want))


def test_pallas_kernel_matches_xla_float_and_grid():
    """Float x over a multi-step grid (N = 2 blocks)."""
    rng = numpy.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 96).astype(numpy.float32))
    w = rng.randn(96, 1024).astype(numpy.float32)
    q, scale = quantize_int8(w)
    got = int8_matmul(x, q, scale, use_pallas=True, interpret=True)
    want = int8_matmul(x, q, scale, use_pallas=False)
    numpy.testing.assert_allclose(numpy.asarray(got),
                                  numpy.asarray(want), rtol=2e-5,
                                  atol=1e-4)


def test_matmul_any_dispatch():
    rng = numpy.random.RandomState(4)
    x = jnp.asarray(rng.randn(2, 3, 64).astype(numpy.float32))
    w = rng.randn(64, 128).astype(numpy.float32)
    dense = matmul_any(x, jnp.asarray(w))
    q, scale = quantize_int8(w)
    quant = matmul_any(x, {"q8": q, "scale": scale})
    assert quant.shape == dense.shape == (2, 3, 128)
    # int8 weights: ~1% relative error on a randn product
    err = numpy.abs(numpy.asarray(quant) - numpy.asarray(dense))
    assert err.mean() < 0.05 * numpy.abs(numpy.asarray(dense)).mean()


def test_generate_int8_matches_quantized_reference_loop():
    """generate(quantize='int8') tokens == a naive recompute loop over
    the SAME quantized weights (the wiring, not the rounding, is under
    test; the XLA path runs on CPU where the auto-gate declines)."""
    from veles_tpu.parallel.decode import generate, quantize_params
    from veles_tpu.parallel.transformer_step import (
        _forward, init_transformer_params)

    heads, embed, vocab = 4, 16, 11
    rng = numpy.random.RandomState(5)
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.3)
    prompt = jnp.asarray(rng.randint(0, vocab, (2, 5)))

    toks, _ = generate(params, table, prompt, heads, n_tokens=6,
                       quantize="int8")
    assert toks.shape == (2, 6)

    qparams = quantize_params(params)
    seq = table[prompt]
    ref = []
    for _ in range(6):
        logits = _forward(qparams, seq, heads, 1, "ulysses")[:, -1]
        tok = jnp.argmax(logits, axis=-1)
        ref.append(tok)
        seq = jnp.concatenate([seq, table[tok][:, None, :]], axis=1)
    numpy.testing.assert_array_equal(
        numpy.asarray(toks), numpy.asarray(jnp.stack(ref, axis=1)))


def test_generate_int8_accepts_prequantized():
    from veles_tpu.parallel.decode import generate, quantize_params
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)

    heads, embed, vocab = 4, 16, 11
    rng = numpy.random.RandomState(6)
    params = init_transformer_params(rng, 1, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.3)
    prompt = jnp.asarray(rng.randint(0, vocab, (1, 4)))
    qparams = quantize_params(params)
    t1, _ = generate(params, table, prompt, heads, n_tokens=3,
                     quantize="int8")
    t2, _ = generate(qparams, table, prompt, heads, n_tokens=3,
                     quantize="int8")
    numpy.testing.assert_array_equal(numpy.asarray(t1),
                                     numpy.asarray(t2))


def _attend_fixture(batch=2, length=7, heads=3, dim=8, seed=8):
    """(q, head-major int8 K/V + scales, equivalent fp K/V, mask)."""
    from veles_tpu.parallel.decode import _quantize_kv

    rng = numpy.random.RandomState(seed)
    q = jnp.asarray(rng.randn(batch, 1, heads, dim).astype(
        numpy.float32))
    k = jnp.asarray(rng.randn(batch, length, heads, dim).astype(
        numpy.float32))
    v = jnp.asarray(rng.randn(batch, length, heads, dim).astype(
        numpy.float32))
    kq, ks = _quantize_kv(k)
    vq, vs = _quantize_kv(v)
    # (B,T,H,D) -> head-major (B,H,D,T); scales (B,T,H) -> (B,H,T)
    to_hm = lambda a: jnp.transpose(a, (0, 2, 3, 1))  # noqa: E731
    return (q, to_hm(kq), jnp.transpose(ks, (0, 2, 1)), to_hm(vq),
            jnp.transpose(vs, (0, 2, 1)), k, v, kq, ks, vq, vs)


def test_cache_attend_scale_folding_matches_explicit_dequant():
    """int8_cache_attend (XLA formulation, head-major layout) folds
    k_scale into the score row and v_scale into the softmax weights;
    it must equal attending against explicitly dequantized fp K/V
    through the plain _cache_attend (pure reassociation + layout)."""
    from veles_tpu.parallel.decode import _cache_attend, _positions_last
    from veles_tpu.ops.quant import int8_cache_attend

    (q, khm, kshm, vhm, vshm, _, _, kq, ks, vq, vs) = _attend_fixture()
    length, dim = kq.shape[1], q.shape[-1]
    inv = 1.0 / numpy.sqrt(dim)
    mask_addend = jnp.zeros(length, jnp.float32)
    got = int8_cache_attend(q * inv, khm, kshm, vhm, vshm, mask_addend)
    deq_k = kq.astype(jnp.float32) * ks[..., None]
    deq_v = vq.astype(jnp.float32) * vs[..., None]
    mask = jnp.ones((1, 1, 1, length), bool)
    # positions-major rows handed over in the slab's order, as every
    # caller with such a cache does
    want = _cache_attend(q, _positions_last(deq_k),
                         _positions_last(deq_v), mask)
    numpy.testing.assert_allclose(numpy.asarray(got),
                                  numpy.asarray(want), rtol=1e-5,
                                  atol=1e-6)


def test_cache_attend_per_row_masks_match_per_row_calls():
    """The (B, T) per-row mask form (the slot engine's per-slot
    lengths): each row must equal a separate call with that row's
    1-D mask."""
    from veles_tpu.ops.quant import int8_cache_attend

    (q, khm, kshm, vhm, vshm, *_) = _attend_fixture(
        batch=2, length=128, heads=2, dim=32, seed=12)
    inv = 1.0 / numpy.sqrt(q.shape[-1])
    lengths = (50, 97)
    masks = jnp.stack([
        jnp.where(jnp.arange(128) <= n, 0.0, -1e30).astype(jnp.float32)
        for n in lengths])
    got = int8_cache_attend(q * inv, khm, kshm, vhm, vshm, masks)
    for row in range(2):
        want = int8_cache_attend(
            q[row:row + 1] * inv, khm[row:row + 1],
            kshm[row:row + 1], vhm[row:row + 1], vshm[row:row + 1],
            masks[row])
        numpy.testing.assert_allclose(
            numpy.asarray(got[row:row + 1]), numpy.asarray(want),
            rtol=2e-5, atol=2e-5)


def test_forced_pallas_that_cannot_run_raises():
    """An explicit use_pallas=True on a shape the kernel cannot take
    used to run the XLA product silently — a forced comparison then
    measured XLA against XLA. It raises, naming the argument."""
    import pytest
    from veles_tpu.ops import quant

    x = jnp.ones((4, 48), jnp.float32)          # k % 32 != 0
    q8 = jnp.ones((48, 512), jnp.int8)
    scale = jnp.ones((512,), jnp.float32)
    with pytest.raises(ValueError, match="use_pallas=True"):
        quant.int8_matmul(x, q8, scale, use_pallas=True, interpret=True)
    x = jnp.ones((4, 64), jnp.float32)
    q8 = jnp.ones((64, 100), jnp.int8)          # n has no lane block
    with pytest.raises(ValueError, match="use_pallas=True"):
        quant.int8_matmul(x, q8, jnp.ones((100,), jnp.float32),
                          use_pallas=True, interpret=True)


def test_quantize_kv_roundtrip_bound():
    from veles_tpu.parallel.decode import _quantize_kv

    rng = numpy.random.RandomState(9)
    x = rng.randn(2, 5, 3, 16).astype(numpy.float32)
    q, scale = _quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and scale.shape == (2, 5, 3)
    err = numpy.abs(numpy.asarray(q, numpy.float32)
                    * numpy.asarray(scale)[..., None] - x)
    assert (err <= numpy.asarray(scale)[..., None] / 2 + 1e-7).all()


def test_generate_int8_kv_runs_and_tracks_fp():
    """int8-kv serving: the fully-quantized loop must stay close to the
    fp32 decode — same first token (clean logit margins at this scale)
    and highly-correlated logits throughout."""
    from veles_tpu.parallel.decode import (decode_step, generate,
                                           init_kv_cache, prefill)
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)

    heads, embed, vocab = 4, 32, 13
    rng = numpy.random.RandomState(10)
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.3)
    prompt = jnp.asarray(rng.randint(0, vocab, (2, 6)))

    toks, cache = generate(params, table, prompt, heads, n_tokens=5,
                           quantize="int8-kv")
    assert toks.shape == (2, 5)
    assert cache["k"].dtype == jnp.int8
    assert int(cache["length"]) == 11

    # logits comparison at the first decode step: quantized cache vs fp
    x = table[prompt]
    fp_logits, fp_cache = prefill(
        params, x, heads, init_kv_cache(2, 2, 11, heads, embed // heads))
    q_logits, q_cache = prefill(
        params, x, heads,
        init_kv_cache(2, 2, 11, heads, embed // heads, quantized=True))
    # prefill attends the exact K/V: logits identical
    numpy.testing.assert_allclose(numpy.asarray(q_logits),
                                  numpy.asarray(fp_logits), rtol=1e-5,
                                  atol=1e-5)
    tok = jnp.argmax(fp_logits, axis=-1)
    x_tok = table[tok][:, None, :]
    fp_step, _ = decode_step(params, x_tok, heads, fp_cache)
    q_step, _ = decode_step(params, x_tok, heads, q_cache)
    fp_np = numpy.asarray(fp_step, numpy.float64)
    q_np = numpy.asarray(q_step, numpy.float64)
    cos = (fp_np * q_np).sum() / (numpy.linalg.norm(fp_np)
                                  * numpy.linalg.norm(q_np))
    assert cos > 0.999
    numpy.testing.assert_array_equal(fp_np.argmax(-1), q_np.argmax(-1))


def test_tp_decode_rejects_quantized_params():
    from veles_tpu.parallel.decode import (make_tp_generate,
                                           quantize_params)
    from veles_tpu.parallel.mesh import build_mesh
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)

    rng = numpy.random.RandomState(7)
    params = quantize_params(
        init_transformer_params(rng, 1, 16, 2, 8))
    table = jnp.asarray(rng.randn(8, 16).astype(numpy.float32))
    mesh = build_mesh(devices=jax.devices()[:2], data=1, model=2)
    run = make_tp_generate(mesh, 2, n_tokens=2)
    with pytest.raises(ValueError):
        run(params, table, jnp.zeros((1, 3), jnp.int32))
