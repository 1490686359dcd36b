"""KV-cache decoding: the scan-decode path must match recomputing the
full causal forward over the growing sequence, token for token."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.parallel.decode import (decode_step, generate,
                                       init_kv_cache, prefill)
from veles_tpu.parallel.transformer_step import (_forward,
                                                 init_transformer_params)

HEADS, EMBED, BLOCKS, VOCAB = 4, 16, 2, 11


@pytest.fixture(scope="module")
def model():
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, BLOCKS, EMBED, HEADS, VOCAB)
    embed_table = jnp.asarray(
        rng.randn(VOCAB, EMBED).astype(numpy.float32) * 0.3)
    return params, embed_table


def test_prefill_matches_full_forward(model):
    params, table = model
    rng = numpy.random.RandomState(1)
    toks = rng.randint(0, VOCAB, (2, 5))
    x = table[jnp.asarray(toks)]
    logits, cache = prefill(params, x, HEADS,
                            init_kv_cache(BLOCKS, 2, 12, HEADS,
                                          EMBED // HEADS))
    full = _forward(params, x, HEADS, 1, "ulysses")
    numpy.testing.assert_allclose(numpy.asarray(logits),
                                  numpy.asarray(full[:, -1]),
                                  rtol=2e-4, atol=2e-5)
    assert int(cache["length"]) == 5


def test_decode_steps_match_growing_forward(model):
    """Each decoded step's logits == the full forward's last position on
    the concatenated sequence (the KV cache changes the computation
    order, not the math)."""
    params, table = model
    rng = numpy.random.RandomState(2)
    toks = rng.randint(0, VOCAB, (3, 4))
    x = table[jnp.asarray(toks)]
    logits, cache = prefill(params, x, HEADS,
                            init_kv_cache(BLOCKS, 3, 10, HEADS,
                                          EMBED // HEADS))
    seq = x
    for _ in range(5):
        tok = jnp.argmax(logits, axis=-1)
        x_tok = table[tok][:, None, :]
        logits, cache = decode_step(params, x_tok, HEADS, cache)
        seq = jnp.concatenate([seq, x_tok], axis=1)
        full = _forward(params, seq, HEADS, 1, "ulysses")
        numpy.testing.assert_allclose(numpy.asarray(logits),
                                      numpy.asarray(full[:, -1]),
                                      rtol=2e-4, atol=2e-5)


def test_generate_greedy_matches_reference_loop(model):
    """generate() (one jitted scan, donated cache) produces the same
    token ids as the naive recompute-everything greedy loop."""
    params, table = model
    rng = numpy.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(0, VOCAB, (2, 6)))
    toks, cache = generate(params, table, prompt, HEADS, n_tokens=7)
    assert toks.shape == (2, 7)
    assert int(cache["length"]) == 13

    seq = table[prompt]
    ref = []
    for _ in range(7):
        logits = _forward(params, seq, HEADS, 1, "ulysses")[:, -1]
        tok = jnp.argmax(logits, axis=-1)
        ref.append(tok)
        seq = jnp.concatenate([seq, table[tok][:, None, :]], axis=1)
    numpy.testing.assert_array_equal(
        numpy.asarray(toks), numpy.asarray(jnp.stack(ref, axis=1)))


def test_generate_rejects_overflow(model):
    params, table = model
    prompt = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError):
        generate(params, table, prompt, HEADS, n_tokens=5, max_len=8)


def test_generate_bf16_matches_bf16_reference(model):
    """The bf16 serving configuration (params/table/cache all bf16 —
    the bench's decode_bfloat16 keys): scan-decode tokens equal the
    bf16 full-recompute loop."""
    params, table = model
    bf16 = jnp.bfloat16
    params16 = jax.tree.map(lambda a: a.astype(bf16), params)
    table16 = table.astype(bf16)
    rng = numpy.random.RandomState(4)
    prompt = jnp.asarray(rng.randint(0, VOCAB, (2, 5)))

    toks, _ = generate(params16, table16, prompt, HEADS, n_tokens=6)

    seq = table16[prompt]
    ref = []
    for _ in range(6):
        logits = _forward(params16, seq, HEADS, 1, "ulysses")[:, -1]
        tok = jnp.argmax(logits, axis=-1)
        ref.append(tok)
        seq = jnp.concatenate([seq, table16[tok][:, None, :]], axis=1)
    numpy.testing.assert_array_equal(
        numpy.asarray(toks), numpy.asarray(jnp.stack(ref, axis=1)))


def test_generate_sampling_reproducible_and_topk_bounded(model):
    """temperature sampling: same key => same tokens; different key =>
    (almost surely) different; top_k=1 degenerates to greedy."""
    params, table = model
    rng = numpy.random.RandomState(5)
    prompt = jnp.asarray(rng.randint(0, VOCAB, (2, 5)))
    key = jax.random.key(42)

    t1, _ = generate(params, table, prompt, HEADS, n_tokens=8,
                     temperature=1.0, key=key)
    t2, _ = generate(params, table, prompt, HEADS, n_tokens=8,
                     temperature=1.0, key=key)
    numpy.testing.assert_array_equal(numpy.asarray(t1),
                                     numpy.asarray(t2))
    t3, _ = generate(params, table, prompt, HEADS, n_tokens=8,
                     temperature=1.0, key=jax.random.key(43))
    assert not numpy.array_equal(numpy.asarray(t1), numpy.asarray(t3))

    greedy, _ = generate(params, table, prompt, HEADS, n_tokens=8)
    top1, _ = generate(params, table, prompt, HEADS, n_tokens=8,
                       temperature=0.7, top_k=1, key=key)
    numpy.testing.assert_array_equal(numpy.asarray(greedy),
                                     numpy.asarray(top1))


def _tier_model(model, tier):
    """(params, table, dtype, quantize, state kwargs) of a serving
    tier: float32, bfloat16, or int8-KV (int8 weights and cache)."""
    from veles_tpu.parallel.decode import quantize_params

    params, table = model
    if tier == "bfloat16":
        cast = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
        return jax.tree.map(cast, params), cast(table), None
    if tier == "int8-kv":
        return quantize_params(params), table, "int8-kv"
    return params, table, None


def _admitted(params, table, quantize, lens, max_len, seed=7):
    """A slot state with one prompt of each of ``lens`` admitted,
    and the prompts."""
    from veles_tpu.parallel.decode import init_slot_state, slot_admit

    rng = numpy.random.RandomState(seed)
    state = init_slot_state(BLOCKS, len(lens), max_len, HEADS,
                            EMBED // HEADS, VOCAB, dtype=table.dtype,
                            quantized=quantize == "int8-kv")
    prompts = []
    for slot, n in enumerate(lens):
        prompts.append(jnp.asarray(rng.randint(0, VOCAB, (1, n))))
        state = slot_admit(params, table, HEADS, state, jnp.int32(slot),
                           table[prompts[-1]])
    return state, prompts


TIERS = ["float32", "bfloat16", "int8-kv"]
# staggered lengths; the last case holds a slot at max_len - 1, whose
# append lands on the lane's last position
SPAN_CASES = [((5, 3), 24), ((1, 9, 4), 24), ((6, 23), 24)]


@pytest.mark.parametrize("lens, max_len", SPAN_CASES,
                         ids=["short", "staggered", "lane_end"])
@pytest.mark.parametrize("tier", TIERS)
def test_slot_step_span_tiling_is_inert(model, tier, lens, max_len):
    """The tiled slot attention contract: any span covering the
    longest live sequence (+1 for the appended token) produces
    bit-identical state updates and emitted tokens vs attending the
    whole max_len lane — masked positions contribute exact zeros."""
    from veles_tpu.parallel.decode import slot_step

    params, table, quantize = _tier_model(model, tier)
    state, _ = _admitted(params, table, quantize, lens, max_len)
    active = jnp.ones((len(lens),), bool)
    full, tok_full = slot_step(params, table, HEADS,
                               jax.tree.map(jnp.copy, state), active)
    spans = [n for n in (8, 16, 24) if n > max(lens)]
    for span in spans:
        tiled, tok_tiled = slot_step(params, table, HEADS,
                                     jax.tree.map(jnp.copy, state),
                                     active, span=span)
        numpy.testing.assert_array_equal(numpy.asarray(tok_tiled),
                                         numpy.asarray(tok_full))
        numpy.testing.assert_array_equal(
            numpy.asarray(tiled["logits"]), numpy.asarray(full["logits"]))
        for name in ("k", "v"):
            for one, other in zip(tiled[name], full[name]):
                numpy.testing.assert_array_equal(numpy.asarray(one),
                                                 numpy.asarray(other))


@pytest.mark.parametrize("tier", TIERS)
def test_slot_step_many_tokens_match_generate(model, tier):
    """Chunks of 4 lockstep steps over staggered slots emit, slot by
    slot, the tokens ``generate`` emits for that prompt alone."""
    from veles_tpu.parallel.decode import slot_step_many

    params, table, quantize = _tier_model(model, tier)
    lens, n_tokens = (5, 3, 9), 8
    # int8-KV generate rounds its cache up to whole lane tiles
    max_len = 128 if quantize else 24
    state, prompts = _admitted(params, table, quantize, lens, max_len)
    active = jnp.ones((len(lens),), bool)
    emitted = []
    for chunk in range(n_tokens // 4):
        state, toks = slot_step_many(
            params, table, HEADS, state, active, 4,
            span=16 if chunk == 0 else max_len)
        emitted.append(numpy.asarray(toks))
    emitted = numpy.concatenate(emitted)                  # (n, S)
    for slot, prompt in enumerate(prompts):
        want, _ = generate(params, table, prompt, HEADS,
                           n_tokens=n_tokens, max_len=max_len,
                           quantize=quantize)
        numpy.testing.assert_array_equal(emitted[:, slot],
                                         numpy.asarray(want)[0])


def _eqns(jaxpr, scope=()):
    """Every equation of a jaxpr and of the jaxprs inside it, with the
    scope names on its name stack."""
    from veles_tpu.observe.xla_stats import scope_names

    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        names = tuple(scope) + tuple(scope_names(stack) if stack else ())
        yield eqn, names
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else (value,)):
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    yield from _eqns(inner, names)


@pytest.mark.parametrize("tier", ["float32", "int8-kv"])
def test_chunk_reads_one_window_per_leaf(model, tier):
    """The structure the chunk program's speed rests on: under
    ``cache.read`` every block produces exactly one value per K/V
    leaf, of the span's size, and no value of a leaf's ``max_len``
    size exists but what each leaf's one write a chunk returns, so
    nothing copies a layer at full length."""
    from veles_tpu.parallel.decode import _kv_names, _slot_step_many

    params, table, quantize = _tier_model(model, tier)
    slots, max_len, span = 3, 32, 16
    state, _ = _admitted(params, table, quantize, (5, 3, 9), max_len)
    names = _kv_names(state)
    assert all(isinstance(state[name], tuple)
               and len(state[name]) == BLOCKS for name in names)
    jaxpr = jax.make_jaxpr(
        lambda st: _slot_step_many(params, table, HEADS, st,
                                   jnp.ones((slots,), bool), 4,
                                   span=span))(state)
    leaf_shapes = {state[name][0].shape for name in names}
    window_shapes = sorted(shape[:-1] + (span,) for shape in leaf_shapes)
    reads, full = [], []
    for eqn, scope in _eqns(jaxpr):
        shapes = [tuple(var.aval.shape) for var in eqn.outvars]
        if "cache.read" in scope:
            reads.extend(shapes)
        if any(shape in leaf_shapes for shape in shapes):
            full.append(eqn.primitive.name)
    # one read per leaf per block; the scan's body is traced once
    assert len(reads) == len(names) * BLOCKS, reads
    assert sorted(set(reads)) == window_shapes
    # full-length values: each leaf's one write a chunk (a loop over
    # the slots round one append), nothing else. The loop of steps
    # itself carries no leaf: it reads them where they lie
    assert set(full) <= {"dynamic_update_slice", "scan"}, set(full)
    assert full.count("dynamic_update_slice") == len(names) * BLOCKS
    assert full.count("scan") == len(names) * BLOCKS


def test_layout_is_decided_once_and_pinned_in_and_out(model):
    """The layout mechanism end to end where it can run off the chip:
    the compiler is asked once per state skeleton, the zeros are built
    in its answer, the programs are found by what the leaves carry,
    every program's K/V come back as they went in, and a program is
    lowered once whether its state is fresh or another program's
    output."""
    import functools

    from veles_tpu.parallel import decode

    params, table = model
    build = functools.partial(
        decode.init_slot_state, BLOCKS, 2, 32, HEADS, EMBED // HEADS,
        VOCAB)
    formats = decode.decide_slot_formats(
        params, table, HEADS, jax.eval_shape(build), 4, 16)
    assert sorted(formats) == ["k", "v"]
    assert decode.decide_slot_formats(
        params, table, HEADS, jax.eval_shape(build), 4, 16) is formats
    state = build(formats=formats)
    fns = decode.slot_fns(state)
    for name in ("k", "v"):
        assert all(leaf.format == formats[name] for leaf in state[name])
    state = decode.slot_admit(params, table, HEADS, state, jnp.int32(0),
                              table[jnp.asarray([[1, 2, 3]])])
    active = jnp.asarray([True, False])
    chunk = fns[2].__wrapped__
    lowered = chunk._cache_size()
    for _ in range(3):
        state, _ = decode.slot_step_many(params, table, HEADS, state,
                                         active, 4, span=16)
        assert decode.slot_fns(state) is fns
        for name in ("k", "v"):
            assert all(leaf.format == formats[name]
                       for leaf in state[name])
    assert chunk._cache_size() == lowered + 1
    # a state that an outer trace holds has nothing to read a place
    # off: the unpinned programs serve it
    assert decode.slot_fns(jax.eval_shape(build)) is not fns


def test_slot_admit_many_matches_single_admits(model):
    """One batched same-bucket admission dispatch produces the same
    slot state as admitting each prompt alone — including duplicate
    padding rows (the host pads groups to powers of two)."""
    from veles_tpu.parallel.decode import (init_slot_state, slot_admit,
                                           slot_admit_many)

    params, table = model
    rng = numpy.random.RandomState(8)
    lens = (5, 7, 3)
    prompts = [rng.randint(0, VOCAB, n) for n in lens]
    bucket = 8
    padded = numpy.zeros((4, bucket), numpy.int32)  # padded to 4 rows
    for j, p in enumerate(prompts + [prompts[-1]]):  # duplicate row
        padded[j, :len(p)] = p
    keys = jax.random.split(jax.random.key(3), 4)
    ref = init_slot_state(BLOCKS, 4, 24, HEADS, EMBED // HEADS, VOCAB)
    for slot, (p, n) in enumerate(zip(prompts, lens)):
        row = numpy.zeros(bucket, numpy.int32)
        row[:n] = p
        ref = slot_admit(params, table, HEADS, ref, jnp.int32(slot),
                         table[jnp.asarray(row)][None],
                         req_key=keys[slot], length=jnp.int32(n))
    batched = init_slot_state(BLOCKS, 4, 24, HEADS, EMBED // HEADS,
                              VOCAB)
    batched = slot_admit_many(
        params, table, HEADS, batched,
        jnp.asarray([0, 1, 2, 2], jnp.int32),
        table[jnp.asarray(padded)],
        keys.at[3].set(keys[2]),
        jnp.asarray(list(lens) + [lens[-1]], jnp.int32))
    numpy.testing.assert_array_equal(numpy.asarray(ref["lengths"]),
                                     numpy.asarray(batched["lengths"]))
    # a batched and a single-row prefill reassociate their matmuls:
    # the logits agree to rounding, everything stored agrees exactly
    numpy.testing.assert_allclose(numpy.asarray(ref["logits"]),
                                  numpy.asarray(batched["logits"]),
                                  rtol=1e-5, atol=1e-6)
    numpy.testing.assert_array_equal(
        numpy.asarray(jax.random.key_data(ref["req_key"])),
        numpy.asarray(jax.random.key_data(batched["req_key"])))
    # the written K/V rows agree wherever a real prompt lives, in
    # every block's leaf (S, H·D, T)
    for name in ("k", "v"):
        assert len(ref[name]) == len(batched[name]) == BLOCKS
        for one, many in zip(ref[name], batched[name]):
            assert one.shape == (4, EMBED, 24)
            for slot, n in enumerate(lens):
                numpy.testing.assert_array_equal(
                    numpy.asarray(one[slot, :, :n]),
                    numpy.asarray(many[slot, :, :n]))


def test_tensor_parallel_decode_smoke_2dev():
    """Cheap TP-decode smoke tier: 2-device mesh, 2 tokens, tiny model —
    fast enough to run on every suite invocation so the TP call path
    (repack → _tp_specs → shard_map) is always exercised."""
    from veles_tpu.parallel.decode import make_tp_generate
    from veles_tpu.parallel.mesh import build_mesh

    rng = numpy.random.RandomState(9)
    heads, embed, vocab = 2, 8, 4
    tp_params = init_transformer_params(rng, 1, embed, heads, vocab)
    tp_table = jnp.asarray(
        rng.randn(vocab, embed).astype(numpy.float32) * 0.3)
    prompt = jnp.asarray(rng.randint(0, vocab, (1, 3)))

    single, _ = generate(tp_params, tp_table, prompt, heads, n_tokens=2)
    mesh = build_mesh(devices=jax.devices()[:2], data=1, model=2)
    run = make_tp_generate(mesh, heads, n_tokens=2)
    sharded = run(tp_params, tp_table, prompt)
    numpy.testing.assert_array_equal(numpy.asarray(sharded),
                                     numpy.asarray(single))


def test_tensor_parallel_decode_matches_single_device(model):
    """Megatron-style TP decode over an 8-device model axis: the
    sharded run's tokens equal the single-device generate()."""
    from veles_tpu.parallel.decode import make_tp_generate
    from veles_tpu.parallel.mesh import build_mesh

    params, table = model
    # vocab 11 doesn't divide 8 — build a TP-compatible model instead
    rng = numpy.random.RandomState(6)
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    heads, embed, vocab = 8, 32, 16
    tp_params = init_transformer_params(rng, 2, embed, heads, vocab)
    tp_table = jnp.asarray(
        rng.randn(vocab, embed).astype(numpy.float32) * 0.3)
    prompt = jnp.asarray(rng.randint(0, vocab, (2, 6)))

    single, _ = generate(tp_params, tp_table, prompt, heads, n_tokens=7)

    mesh = build_mesh(devices=jax.devices()[:8], data=1, model=8)
    run = make_tp_generate(mesh, heads, n_tokens=7)
    sharded = run(tp_params, tp_table, prompt)
    numpy.testing.assert_array_equal(numpy.asarray(sharded),
                                     numpy.asarray(single))
    _ = params, table


def test_tensor_parallel_rejects_indivisible(model):
    from veles_tpu.parallel.decode import make_tp_generate
    from veles_tpu.parallel.mesh import build_mesh

    params, table = model  # HEADS=4, vocab 11: not divisible by 8
    mesh = build_mesh(devices=jax.devices()[:8], data=1, model=8)
    run = make_tp_generate(mesh, HEADS, n_tokens=3)
    with pytest.raises(ValueError):
        run(params, table, jnp.zeros((1, 4), jnp.int32))


# -- the chunk's block write: the Pallas call against the loop ----------------

def _reference_model(name):
    """A benchmark model's reference at its rehearsal sizes, float32:
    ``(params, table, heads)``."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_"),
        os.path.join(root, "benchmark/references/%s.py" % name))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)
    with open(os.path.join(root, "benchmark/configs/%s.json" % name)) \
            as fin:
        config = json.load(fin)
    small = dict(config["rehearsal"])
    config["serving"] = dict(config["serving"], **small.pop("serving"))
    config.update(small)
    params, table = jax.tree.map(lambda a: a.astype(jnp.float32),
                                 reference.init_params(5, config))
    return params, table, config["n_head"]


def _two_chunks(params, table, heads):
    """Prompts of 5 and 125 tokens in slots 0 and 1, a chunk of 4, one
    of 126 admitted into slot 2 (its next chunk straddles a 128-lane
    boundary; slot 3 stays idle), a second chunk: ``(the write path,
    tokens (8, S), state)``."""
    from veles_tpu.parallel import blocks
    from veles_tpu.parallel.decode import (init_slot_state, slot_admit,
                                           slot_step_many,
                                           slot_write_path, split_emitted)

    rng = numpy.random.RandomState(4)
    vocab = table.shape[0]
    state = init_slot_state(len(params["blocks"]), 4, 256, heads,
                            table.shape[1] // heads, vocab,
                            dtype=table.dtype, arch=blocks.arch_of(params))
    tokens = []
    for slots in ((0, 1), (2,)):
        for slot in slots:
            prompt = rng.randint(0, vocab, (1, (5, 125, 126)[slot]))
            state = slot_admit(params, table, heads, state,
                               jnp.int32(slot), table[jnp.asarray(prompt)])
        active = jnp.arange(4) <= max(slots)
        state, emitted = slot_step_many(params, table, heads, state,
                                        active, 4, span=256)
        tokens.append(numpy.asarray(split_emitted(emitted)[0]))
    return slot_write_path(state, 4), numpy.concatenate(tokens), state


@pytest.mark.parametrize("kind", ["mha", "latent", "gqa"])
def test_the_write_kernel_leaves_the_loops_state(kind, model, monkeypatch):
    """GPT-2's block, latent attention (JoyAI's) and grouped-query
    attention beside short convolutions (LFM2's), each at a toy size:
    two chunks with an admission between them give the same tokens and
    a state equal bit for bit, whether the chunk's blocks go to the
    slab by ``ops/slab_write``'s kernel (interpreted, the platform
    steered) or by the loop."""
    from veles_tpu.ops import slab_write

    if kind == "mha":
        params, table = model
        heads = HEADS
    else:
        params, table, heads = _reference_model(
            "joyai-llm-flash" if kind == "latent" else "lfm2-8b-a1b")
    jax.clear_caches()
    path, want, loop_state = _two_chunks(params, table, heads)
    assert path == "loop"
    monkeypatch.setattr(slab_write, "on_tpu", lambda: True)
    monkeypatch.setattr(slab_write, "device_kind", lambda: "TPU v5 lite")
    jax.clear_caches()
    try:
        path, got, state = _two_chunks(params, table, heads)
    finally:
        jax.clear_caches()
    assert path == "kernel"
    numpy.testing.assert_array_equal(got, want)
    want_bits, got_bits = _bits(loop_state), _bits(state)
    assert len(got_bits) == len(want_bits)
    for one, other in zip(got_bits, want_bits):
        assert one.shape == other.shape
        assert numpy.array_equal(one, other)


def _bits(state):
    """Every leaf of a slot state as its bytes (the sampling keys as
    their key data)."""
    def raw(leaf):
        if jnp.issubdtype(leaf.dtype, jax.dtypes.prng_key):
            leaf = jax.random.key_data(leaf)
        return numpy.asarray(leaf).view(numpy.uint8)

    return [raw(leaf) for leaf in jax.tree.leaves(state)]
