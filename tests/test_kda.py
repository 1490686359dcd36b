"""The gated delta rule with a decay a channel (``ops/delta_rule.py``, the
``"kda"`` kind of ``parallel/blocks.py``) beside a gated NoPE layer,
through the slot engine and ``ContinuousDecoder``, against the plain
reference.

Everything here is float32 on the CPU at the benchmark configuration's
rehearsal sizes (``benchmark/configs/solar-open2-250b.json``: 4 heads of
16, 4 of 16 experts held), on seeded weights from the reference's own
``init_params`` widened to float32, so that what a comparison sees is
the order of the arithmetic and no rounding of operands. The reference
(``benchmark/references/solar-open2-250b.py``) runs the delta rule as
its recurrence, a position at a time: no chunk, no WY form.
"""

import dataclasses
import functools
import hashlib
import importlib.util
import json
import os
import re

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import delta_rule
from veles_tpu.parallel import blocks, decode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: float32 sums of a few hundred products of O(1) terms, in another
#: order on each side (a chunk's triangular system against the
#: recurrence)
CLOSE = dict(rtol=2e-4, atol=2e-4)
#: what a planted fault moves a logit by at the least
FAULT = 1e-3
SLOTS, MAX_LEN, BUCKET = 4, 64, 32


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _config(name):
    with open(os.path.join(ROOT, "benchmark/configs/%s.json" % name)) as fin:
        config = json.load(fin)
    small = dict(config["rehearsal"])
    config["serving"] = dict(config["serving"], **small.pop("serving"))
    config.update(small)
    return config


@pytest.fixture(scope="module")
def reference():
    return _load("benchmark/references/solar-open2-250b.py",
                 "solar_open2_reference")


@pytest.fixture(scope="module")
def config():
    return _config("solar-open2-250b")


@pytest.fixture(scope="module")
def model(reference, config):
    params, table = reference.init_params(5, config)
    return jax.tree.map(lambda a: a.astype(jnp.float32), (params, table))


def _parts(seed, t, heads=4, d=16, batch=1, strong=False):
    """Seeded q, k, v, log-decays and write strengths of a layer as
    ``project`` and the convolutions make them: q and k of unit length
    (q scaled), decays of 1e-3 .. 0.3 a position (``strong``: up to 9),
    write strengths in (0, 2)."""
    rng = numpy.random.RandomState(seed)
    q, k, v = (rng.randn(batch, t, heads, d) for _ in range(3))
    q = q / numpy.linalg.norm(q, axis=-1, keepdims=True) / numpy.sqrt(d)
    k = k / numpy.linalg.norm(k, axis=-1, keepdims=True)
    g = -numpy.exp(rng.uniform(numpy.log(1e-3),
                               numpy.log(9.0 if strong else 0.3),
                               (batch, t, heads, d)))
    beta = 2.0 / (1.0 + numpy.exp(-rng.randn(batch, t, heads)))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _recurrence(q, k, v, g, beta, steps=None, state_dtype=jnp.float32):
    """Position by position through ``delta_rule.step`` from a zero
    state: ``(y (B, T, H, d), S)``; the state kept in ``state_dtype``
    between steps."""
    batch, t, heads, d = q.shape
    held = jnp.zeros((batch, heads, d, d), state_dtype)
    active = jnp.ones((batch,), bool)

    @jax.jit
    def one(held, qt, kt, vt, gt, bt):
        y, held = delta_rule.step(qt, kt, vt, gt, bt,
                                  held.astype(jnp.float32), active)
        return y, held.astype(state_dtype)

    ys = []
    for i in range(t if steps is None else steps):
        y, held = one(held, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        ys.append(numpy.asarray(y))
    return numpy.stack(ys, 1), numpy.asarray(held, numpy.float32)


# -- the chunked form against the recurrence ---------------------------------

@pytest.mark.parametrize("t, strong", [
    (40, False), (130, True), (16, False), (64, True)],
    ids=["t40", "t130_strong", "t16", "t64_strong"])
def test_the_chunked_form_is_the_recurrence(t, strong):
    """Whole chunks, a last chunk that ends mid-way, a sequence shorter
    than a chunk, and decays of up to e^-9 a position (no ``1/exp(G)``
    is formed, so nothing overflows)."""
    q, k, v, g, beta = _parts(t, t, batch=2, strong=strong)
    y, _ = delta_rule.prompt(q, k, v, g, beta)
    want, _ = _recurrence(q, k, v, g, beta)
    numpy.testing.assert_allclose(numpy.asarray(y), want, **CLOSE)


@pytest.mark.parametrize("strong", [False, True], ids=["mild", "strong"])
def test_the_pairs_take_each_pair_s_own_decay(strong):
    """``_pairs`` is ``Σ_c x_t,c k_i,c exp(G_t,c - G_i,c)`` for ``i <=
    t``, pair by pair, inside a sub-chunk and across sub-chunks alike,
    with decays of up to e^-9 a position."""
    q, k, _, g, _ = _parts(3, 64, strong=strong)
    q, k, g = (a[0].swapaxes(0, 1) for a in (q, k, g))       # (H, c, d)
    G = jnp.cumsum(g, 1)
    got = numpy.asarray(delta_rule._pairs(q, k, G))
    q, k, G = (numpy.asarray(a, numpy.float64) for a in (q, k, G))
    at = numpy.arange(64)
    below = at[:, None] >= at[None, :]
    want = numpy.einsum("htc,hic,htic->hti", q, k, numpy.exp(numpy.where(
        below[None, :, :, None], G[:, :, None] - G[:, None, :], -numpy.inf)))
    numpy.testing.assert_allclose(got, want, **CLOSE)


@pytest.mark.parametrize("chunk", [64, 16])
def test_the_state_after_a_row_is_the_state_at_its_true_length(
        monkeypatch, chunk):
    """Right-padded rows of unequal length in one group, one ending
    mid-chunk: each row's state is what the recurrence holds after its
    own last position, whatever the padding holds."""
    monkeypatch.setattr(delta_rule, "CHUNK", chunk)
    q, k, v, g, beta = _parts(5, 70, batch=3)
    lengths = numpy.asarray([70, 7, 37])
    live = jnp.asarray(numpy.arange(70)[None] < lengths[:, None])
    _, got = delta_rule.prompt(q, k, v, g, beta, live)
    for row, length in enumerate(lengths):
        _, held = _recurrence(*(a[row:row + 1] for a in (q, k, v, g, beta)),
                              steps=length)
        numpy.testing.assert_allclose(numpy.asarray(got[row]), held[0],
                                      **CLOSE)


def test_an_idle_lane_keeps_its_state_bit_for_bit():
    q, k, v, g, beta = _parts(6, 1, batch=3)
    rng = numpy.random.RandomState(0)
    held = jnp.asarray(rng.randn(3, 4, 16, 16).astype(numpy.float32))
    active = jnp.asarray([True, False, True])
    _, new = delta_rule.step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             beta[:, 0], held, active)
    assert numpy.array_equal(numpy.asarray(new[1]), numpy.asarray(held[1]))
    assert not numpy.array_equal(numpy.asarray(new[0]),
                                 numpy.asarray(held[0]))


# -- the model through prefill and decode ---------------------------------------

def _prompts(config, lengths, seed=1):
    rng = numpy.random.RandomState(seed)
    return [rng.randint(0, config["vocab_size"], n).tolist()
            for n in lengths]


def _served(params, table, heads, prompts, chunk, chunks):
    """The prompts admitted as one right-padded group, then ``chunks``
    chunks of ``chunk`` steps: ``(logits before each chunk (S, chunks,
    V), tokens (S, chunk * chunks))``."""
    state = decode.init_slot_state(
        len(params["blocks"]), SLOTS, MAX_LEN, heads,
        table.shape[1] // heads, table.shape[0], dtype=table.dtype,
        arch=blocks.arch_of(params))
    padded = numpy.zeros((SLOTS, BUCKET), numpy.int32)
    for j, prompt in enumerate(prompts):
        padded[j, :len(prompt)] = prompt
    state = decode.slot_admit_many(
        params, table, heads, state, jnp.arange(SLOTS, dtype=jnp.int32),
        table[jnp.asarray(padded)], jax.random.split(jax.random.key(0),
                                                     SLOTS),
        jnp.asarray([len(p) for p in prompts], jnp.int32))
    active = jnp.ones((SLOTS,), bool)
    logits, tokens = [], []
    for _ in range(chunks):
        logits.append(numpy.asarray(state["logits"]))
        state, emitted = decode.slot_step_many(params, table, heads, state,
                                               active, chunk)
        tokens.append(numpy.asarray(decode.split_emitted(emitted)[0]))
    return numpy.stack(logits, 1), numpy.concatenate(tokens, 0).T


def _apart(reference, config, params, table, prompts, chunk=8, chunks=3,
           served=None):
    """The widest distance, over the lanes and the chunks' first steps,
    between the logits served with ``served`` (None: ``params``) and
    the reference's full forward with ``params``."""
    logits, tokens = _served(params if served is None else served, table,
                             config["n_head"], prompts, chunk, chunks)
    widest = 0.0
    for lane, prompt in enumerate(prompts):
        want = numpy.asarray(reference.logits_after(
            dict(config, serving=dict(config["serving"],
                                      n_tokens=chunk * chunks)),
            params, table, prompt, tokens[lane].tolist()))
        widest = max(widest, float(numpy.abs(
            want[::chunk] - logits[lane]).max()))
    return widest, logits, tokens


@pytest.mark.parametrize("lengths, chunk", [
    # a bucket-length row, one that ends mid-chunk of the delta rule's
    # chunk form, rows shorter than the convolution's taps
    ((1, 3, 20, 32), 8),
    ((2, 17, 32, 12), 3),
], ids=["chunk8", "chunk3"])
def test_prefill_then_decode_is_the_reference_s_full_forward(
        reference, config, model, lengths, chunk):
    """Right-padded rows admitted as one group, then chunks of steps
    through the state and the convolutions' tails: at each chunk's
    first step the slot's logits are the reference's over the whole
    sequence so far (the recurrence a position at a time)."""
    params, table = model
    widest, logits, _ = _apart(reference, config, params, table,
                               _prompts(config, lengths), chunk=chunk,
                               chunks=24 // chunk)
    assert widest <= CLOSE["atol"] + CLOSE["rtol"] * numpy.abs(
        logits).max(), widest


def test_the_slot_state_holds_the_delta_rule_beside_the_global_rows(
        config, model):
    params, table = model
    heads, d = config["n_head"], config["head_dim"]
    state = decode.init_slot_state(
        4, SLOTS, MAX_LEN, heads, table.shape[1] // heads, table.shape[0],
        dtype=table.dtype, arch=blocks.arch_of(params))
    assert decode._kv_names(state) == ["k_all", "v_all"]
    fixed = state[decode.FIXED]
    assert [leaf.shape for leaf in fixed["kda_S"]] \
        == [(SLOTS, heads, d, d)] * 3
    assert {leaf.dtype for leaf in fixed["kda_S"]} \
        == {jnp.dtype(jnp.float32)}
    assert [leaf.shape for leaf in fixed["kda_conv"]] \
        == [(SLOTS, 3 * 3 * heads * d)] * 3
    holds = decode.slot_holds(params, state)
    assert holds["block_kinds"] == {"nope": 1, "kda": 3}
    assert holds["slot_row_bytes_per_position"] \
        == 2 * config["num_key_value_heads"] * d * 4
    assert holds["slot_fixed_state_bytes"] \
        == 3 * (heads * d * d * 4 + 9 * heads * d * 4)
    assert decode.slot_state_path(params, state) is None


# -- the held shares add up to the layer ----------------------------------------

def test_the_held_shares_sum_to_the_uncut_layer(reference, config, model):
    """Four chips' shares ``(0, 4) .. (12, 4)`` of a block, the mixer and
    the shared expert counted once: the uncut block, all 16 experts
    held (guide section 4); and the reference's forward, all experts
    held, is the program's."""
    params, table = model
    routed = config["routed_experts"]
    whole = reference.init_params(5, dict(config, n_routed_experts=routed))[0]
    whole = jax.tree.map(lambda a: a.astype(jnp.float32), whole)
    arch = blocks.arch_of(whole)
    rng = numpy.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 24, config["hidden_size"]), jnp.float32)
    positions = jnp.arange(24)[None]
    count = config["n_routed_experts"]
    for index in (0, 1):            # the GQA block and a KDA block
        blk = whole["blocks"][index]
        kind = blocks.block_kinds(arch, 4)[index]

        def block(held, experts, blk=blk, kind=kind):
            share = dataclasses.replace(arch, held=held)
            return blocks.block_forward(share, dict(blk, experts=experts),
                                        x, config["n_head"], positions,
                                        kind=kind)[0] - x

        parts = [block((first, count), jax.tree.map(
            lambda w, at=first: w[at:at + count], blk["experts"]))
            for first in range(0, routed, count)]
        alone = block((0, count), jax.tree.map(lambda w: w[:0],
                                               blk["experts"]))
        want = block((0, routed), blk["experts"])
        got = sum(parts) - (len(parts) - 1) * alone
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(want), **CLOSE)
    stacked = [dict(b, first=jnp.int32(0)) for b in whole["blocks"]]
    tokens = jnp.arange(24) % config["vocab_size"]
    ref_logits = reference._logits_at(
        stacked, whole["norm_w"], whole["head"], table, tokens,
        jnp.arange(24), reference.sizes(dict(config,
                                             n_routed_experts=routed)),
        "float32")
    served = blocks.head(arch, whole, functools.reduce(
        lambda h, b: blocks.block_forward(
            arch, b[1], h, config["n_head"], positions,
            kind=blocks.block_kinds(arch, 4)[b[0]])[0],
        enumerate(whole["blocks"]), table[tokens][None]), table)[0]
    numpy.testing.assert_allclose(numpy.asarray(served),
                                  numpy.asarray(ref_logits), **CLOSE)


# -- planted faults -------------------------------------------------------------

def _beta_without_its_double(params, monkeypatch):
    monkeypatch.setattr(blocks.DeltaRule, "write_scale", 1.0)
    return params


def _decay_after_the_write(params, monkeypatch):
    def step(q, k, v, g, beta, held, active):
        beta = jnp.where(active[:, None], beta, 0.0)
        decay = jnp.where(active[:, None, None], jnp.exp(g), 1.0)
        err = v - jnp.einsum("shkv,shk->shv", held, k)
        held = (held + (beta[..., None] * k)[..., None] * err[..., None, :]) \
            * decay[..., None]
        return jnp.einsum("shkv,shk->shv", held, q), held

    def prompt(q, k, v, g, beta, live=None):
        batch, t = q.shape[:2]
        held = jnp.zeros(q.shape[:1] + q.shape[2:] + q.shape[-1:])
        ys = []
        for i in range(t):
            on = jnp.ones((batch,), bool) if live is None else live[:, i]
            y, held = step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i],
                           held, on)
            ys.append(y)
        return jnp.stack(ys, 1), held

    monkeypatch.setattr(delta_rule, "step", step)
    monkeypatch.setattr(delta_rule, "prompt", prompt)
    return params


def _k_not_normalised(params, monkeypatch):
    l2 = blocks._l2
    calls = iter(range(1 << 30))

    def norm(x):
        # q first, then k, in each pass of the streams
        return l2(x) if next(calls) % 2 == 0 else x

    monkeypatch.setattr(blocks, "_l2", norm)
    return params


def _conv_tap_off_by_one(params, monkeypatch):
    return dict(params, blocks=[
        dict(blk, conv_w=jnp.roll(blk["conv_w"], 1, axis=0))
        if "conv_w" in blk else blk for blk in params["blocks"]])


def _gqa_gate_left_off(params, monkeypatch):
    return dict(params, blocks=[
        {key: value for key, value in blk.items() if key != "wgate"}
        for blk in params["blocks"]])


def _state_kept_in_bfloat16(params, monkeypatch):
    real = delta_rule.step

    def rounded(held):
        return held.astype(jnp.bfloat16).astype(jnp.float32)

    def step(q, k, v, g, beta, held, active):
        y, held = real(q, k, v, g, beta, rounded(held), active)
        return y, rounded(held)

    def prompt(q, k, v, g, beta, live=None):
        batch, t = q.shape[:2]
        held = jnp.zeros(q.shape[:1] + q.shape[2:] + q.shape[-1:])
        ys = []
        for i in range(t):
            on = jnp.ones((batch,), bool) if live is None else live[:, i]
            y, held = step(q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i],
                           held, on)
            ys.append(y)
        return jnp.stack(ys, 1), held

    monkeypatch.setattr(delta_rule, "step", step)
    monkeypatch.setattr(delta_rule, "prompt", prompt)
    return params


@pytest.mark.parametrize("plant", [
    _beta_without_its_double, _decay_after_the_write, _k_not_normalised,
    _conv_tap_off_by_one, _gqa_gate_left_off, _state_kept_in_bfloat16,
], ids=lambda plant: plant.__name__.strip("_"))
def test_a_planted_fault_fails_the_comparison_the_program_passes(
        reference, config, model, monkeypatch, plant):
    params, table = model
    prompts = _prompts(config, (1, 3, 20, 32))
    jax.clear_caches()
    try:
        faulty = plant(params, monkeypatch)
        widest, _, _ = _apart(reference, config, params, table, prompts,
                              served=faulty)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert widest > FAULT, widest


def test_a_bfloat16_state_over_512_steps_reads_not_correct():
    """The sixth fault: the state kept in bfloat16 between steps loses
    the corrections (an addend below 2^-8 of the sum is dropped);
    float32 keeps them."""
    q, k, v, g, beta = _parts(8, 512, heads=2, d=8)
    g = jnp.full_like(g, -1.0 / 400.0)              # a long memory
    want, _ = _recurrence(q, k, v, g, beta)
    got, _ = delta_rule.prompt(q, k, v, g, beta)
    assert numpy.allclose(numpy.asarray(got)[:, -64:], want[:, -64:],
                          **CLOSE)
    low, _ = _recurrence(q, k, v, g, beta, state_dtype=jnp.bfloat16)
    assert not numpy.allclose(low[:, -64:], want[:, -64:], **CLOSE)


# -- through ContinuousDecoder --------------------------------------------------

def _decoder(model, config, slots=2, **kwargs):
    from veles_tpu.serving import ContinuousDecoder

    params, table = model
    return ContinuousDecoder(params, table, config["n_head"], slots=slots,
                             max_len=MAX_LEN, n_tokens=6, **kwargs)


@pytest.mark.parametrize("least, n, bucket", [
    (32, 3, 32), (32, 33, 64), (32, 64, 64), (0, 3, 16), (128, 3, 64)],
    ids=["floor", "past_it", "max_len", "none", "clamped"])
def test_a_prompt_shorter_than_the_least_bucket_pads_to_it(
        model, config, least, n, bucket):
    """``Arch.prompt_bucket`` is the least bucket an admission pads a
    prompt to; past it the power of two; ``max_len`` clamps both."""
    params, table = model
    floored = dict(params, arch=dataclasses.replace(
        params["arch"], prompt_bucket=least))
    assert _decoder((floored, table), config).bucket_for(n) == bucket


def test_admit_chunk_collect_retire_and_readmit_into_the_same_slot(
        reference, config, model):
    """Five requests through two slots, so that every slot is taken
    again: each answer is the reference's own greedy continuation, so
    nothing of a slot's old state or convolution tail stayed; the books
    say what a slot holds and how the admissions went."""
    params, table = model
    decoder = _decoder(model, config)
    prompts = _prompts(config, (3, 17, 1, 40, 30), seed=2)
    rids = [decoder.submit(numpy.asarray(p), 6) for p in prompts]
    decoder.drain_pipelined(4)
    assert not decoder.busy and sorted(decoder._free) == [0, 1]
    for rid, prompt in zip(rids, prompts):
        gaps = reference.served_gaps(config, params, table, prompt,
                                     decoder.results[rid])
        assert len(decoder.results[rid]) == 6 and gaps.max() <= 2e-4
    assert decoder.slot_holds["block_kinds"] == {"nope": 1, "kda": 3}
    assert decoder.state_paths is None
    # three KDA blocks, a chunk a row of the 32 bucket (the
    # configuration's least at these sizes) or of the 64
    assert decoder.kda_prompt_chunks >= 3 * 5
    assert blocks.prompt_chunks(params, 2, 64) == 3 * 2 * 1
    assert blocks.prompt_chunks(params, 1, 8192) == 3 * 128
    assert decoder.prompt_paths["xla"] >= 1


@pytest.mark.parametrize("kwargs, tier, lacks", [
    (dict(paged=True), r"paged=True \(the page pool\)",
     "keeps no row a position"),
    (dict(paged=True, prefix_cache="a cache"), r"paged=True",
     "no table indexes"),
    (dict(quantize="int8"), "quantize='int8'",
     "a float32 state that the delta rule corrects"),
    (dict(mesh="a mesh"), r"mesh= \(tensor-parallel serving\)",
     "would shard over heads"),
], ids=["paged", "prefix", "int8", "mesh"])
def test_the_tiers_built_on_gpt2_s_leaves_refuse_the_kind_by_name(
        config, model, kwargs, tier, lacks):
    with pytest.raises(ValueError) as refused:
        _decoder(model, config, **kwargs)
    text = str(refused.value)
    assert re.search(tier, text), text
    assert "3 x 'kda'" in text and "for kind 'kda'" in text, text
    assert lacks in text, text


def test_the_prefix_cache_s_refusal_says_what_a_snapshot_would_take(model):
    with pytest.raises(ValueError) as refused:
        blocks.require_gpt2(model[0], "prefix_cache=", tier="prefix")
    assert "would have to be snapshot" in str(refused.value) \
        and "4 MB a slot a layer" in str(refused.value)


# -- the other models trace as they did ---------------------------------------

#: sha256 of the jaxpr text of each program at the configuration's
#: rehearsal sizes, as the tree before the delta rule and the gated
#: global layer traced them (an admission of 2 rows of 16, a chunk of 4
#: steps over 32 positions of a 4 x 64 slab), but for the routed
#: experts' two gathers, which take their rows by clipped index: the
#: indices are in range, and the fill mode's select over the gathered
#: rows goes (``ops/moe.routed_experts``). A held share's narrower rows
#: (``moe.held_rows``) are the TPU's rule and do not trace here.
PROGRAMS = {
    "command-a-plus-05-2026.admit":
        "6c7f7b3bcb5aab3c29eb96905ee9d4eb671e5d969c25256ff985db0af28ce104",
    "command-a-plus-05-2026.chunk":
        "c04c1e61456b1f50e24febbb41d46f8418cd3bb107a130d72b0ca01917db6e84",
    "brumby-14b-base.admit":
        "3fb3c36fd1bfc3332439189c1e8d01b5c17547ef4081a88b4474c15769ed9f18",
    "brumby-14b-base.chunk":
        "0a9286b6e479b3e70d2c9658737a7a7ea36e90cf2f7650442c79b351f484845b",
    "joyai-llm-flash.admit":
        "5bd79f99b3b2eea40bc7f08b14903b71f13603106e43a0e73f55780b683f0baf",
    "joyai-llm-flash.chunk":
        "2a24e1f2b6a523a889277511b8b80e3003f4f35d1458a4f4842052e22def27d1",
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_a_model_without_the_new_kind_traces_as_before(program):
    name, which = program.split(".")
    config = _config(name)
    params, table = _load("benchmark/references/%s.py" % name,
                          "reference_" + name.replace("-", "_")
                          ).init_params(3, config)
    heads = config["n_head"]
    state = jax.eval_shape(lambda: decode.init_slot_state(
        len(params["blocks"]), 4, 64, heads, table.shape[1] // heads,
        table.shape[0], dtype=table.dtype, arch=blocks.arch_of(params)))
    if which == "admit":
        traced = jax.make_jaxpr(decode._slot_admit_many,
                                static_argnums=(2,))(
            params, table, heads, state, jnp.zeros((2,), jnp.int32),
            jnp.zeros((2, 16, table.shape[1]), table.dtype),
            jax.random.split(jax.random.key(0), 2),
            jnp.zeros((2,), jnp.int32))
    else:
        traced = jax.make_jaxpr(functools.partial(
            decode._slot_step_many, n=4, temperature=1.0, sample=False,
            top_k=0, span=32), static_argnums=(2,))(
            params, table, heads, state, jnp.zeros((4,), bool))
    assert hashlib.sha256(str(traced).encode()).hexdigest() \
        == PROGRAMS[program]
