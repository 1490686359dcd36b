"""Power retention (``ops/retention.py``, the ``"ret"`` kind of
``parallel/blocks.py``) against its attention form, and a model with
no row a position through the slot engine and ``ContinuousDecoder``.

Everything here is float32 on the CPU at the benchmark configuration's
rehearsal sizes (``benchmark/configs/brumby-14b-base.json``), on seeded
weights from the reference's own ``init_params`` widened to float32,
so that what a comparison sees is the order of the arithmetic and no
rounding of operands. The reference (``benchmark/references/
brumby-14b-base.py``) has no feature map, no state and no chunk: the
weights ``a[t, j]`` as a ``T x T`` matrix.
"""

import importlib.util
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import retention
from veles_tpu.parallel import blocks, decode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: float32 sums of a few thousand products of O(1) terms, in another
#: order on each side (a state against a row of weights): 1e-4 of the
#: values' size is a few hundred roundings
CLOSE = dict(rtol=2e-4, atol=2e-4)
#: the most a served token's reference logit may lie below the
#: reference's best: logits of O(1) agreeing to CLOSE
GAP = 2e-4
SLOTS, MAX_LEN, BUCKET = 4, 64, 16


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("benchmark/references/brumby-14b-base.py",
                 "brumby_reference")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmark/configs/brumby-14b-base.json")) as fin:
        config = json.load(fin)
    small = dict(config["rehearsal"])
    config["serving"] = dict(config["serving"], **small.pop("serving"))
    config.update(small)
    return config


@pytest.fixture(scope="module")
def model(reference, config):
    params, table = reference.init_params(5, config)
    return jax.tree.map(lambda a: a.astype(jnp.float32), (params, table))


def _parts(seed, t, heads=4, groups=2, d=16, batch=1):
    """Seeded q, k, v and log-gates of a layer, as ``project`` makes
    them: q and k of unit mean square, memories of 2..200 positions."""
    rng = numpy.random.RandomState(seed)
    q = rng.randn(batch, t, heads, d).astype(numpy.float32)
    k = rng.randn(batch, t, groups, d).astype(numpy.float32)
    v = rng.randn(batch, t, groups, d).astype(numpy.float32)
    log_g = -1.0 / numpy.exp(rng.uniform(
        numpy.log(2.0), numpy.log(200.0), (batch, t, groups)))
    return tuple(jnp.asarray(a) for a in
                 (q, k, v, log_g.astype(numpy.float32)))


def _attention_form(reference, q, k, v, log_g):
    """The reference's ``retention`` a row at a time, K/V heads
    repeated: ``(B, T, H*d)``."""
    rep = q.shape[2] // k.shape[2]
    out = [reference.retention(
        q[b], *(jnp.repeat(a[b], rep, axis=1) for a in (k, v, log_g)))
        for b in range(q.shape[0])]
    return numpy.asarray(jnp.stack(out)).reshape(q.shape[:2] + (-1,))


def _recurrence(q, k, v, log_g, steps=None, **fault):
    """Position by position through ``retention.step`` from an empty
    state: ``(y (T, H*d), S, z)`` of row 0. ``fault`` plants one:
    ``gate_new`` (the gate applied to the new term too), ``z_kept``
    (``z`` not decayed), ``dtype`` (the state's type)."""
    _, t, heads, d = q.shape
    groups = k.shape[2]
    dtype = fault.get("dtype", jnp.float32)
    held = jnp.zeros((1, groups, d, retention.features(d)), dtype)
    norm = jnp.zeros((1, groups, retention.features(d)), dtype)
    active = jnp.ones((1,), bool)

    @jax.jit
    def one(held, norm, qt, kt, vt, gt):
        if fault.get("gate_new"):
            # g (S + phi(k) v / d): the new term decays with the old
            kt_, vt_ = kt, vt * jnp.exp(gt)[..., None]
            y, held, norm = retention.step(qt, kt_, vt_, gt, held, norm,
                                           active)
            return y, held, norm
        before = norm
        y, held, norm = retention.step(qt, kt, vt, gt,
                                       held.astype(jnp.float32),
                                       norm.astype(jnp.float32), active)
        if fault.get("z_kept"):
            norm = before + retention.phi(kt) / d
            y = None
        return y, held.astype(dtype), norm.astype(dtype)

    ys = []
    for i in range(t if steps is None else steps):
        y, held, norm = one(held, norm, q[:, i], k[:, i], v[:, i],
                            log_g[:, i])
        ys.append(y)
    return ys, held, norm


@pytest.mark.parametrize("d", [8, 16, 128])
def test_feature_map_squares_the_score(d):
    rng = numpy.random.RandomState(d)
    q = rng.randn(7, d).astype(numpy.float32)
    k = rng.randn(7, d).astype(numpy.float32)
    got = numpy.sum(numpy.asarray(retention.phi(jnp.asarray(q)))
                    * numpy.asarray(retention.phi(jnp.asarray(k))), -1)
    want = numpy.sum(q.astype(numpy.float64) * k, -1) ** 2
    assert retention.phi(jnp.asarray(q)).shape == (
        7, retention.features(d))
    # float32 rounding of a sum of d^2 / 2 products
    numpy.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4)


def test_an_odd_head_dim_and_another_degree_are_refused_by_name():
    with pytest.raises(ValueError, match="head_dim 7 is odd"):
        retention.phi(jnp.ones((7,)))
    arch = blocks.Arch(layers="ret", kv_heads=2, power=3)
    with pytest.raises(ValueError, match="degree 3"):
        decode.init_slot_state(1, 2, 16, 4, 16, 11, arch=arch)


@pytest.mark.parametrize("chunk", [1024, 16, 12])
def test_the_chunked_form_is_the_attention_form(reference, monkeypatch,
                                                chunk):
    """One chunk (no state at all), whole chunks through the state,
    and a chunk length the sequence is no multiple of."""
    monkeypatch.setattr(retention, "CHUNK", chunk)
    q, k, v, log_g = _parts(3, 48, batch=2)
    got = numpy.asarray(retention.prompt(q, k, v, log_g))
    want = _attention_form(reference, q, k, v, log_g)
    numpy.testing.assert_allclose(got, want, **CLOSE)


def test_the_recurrence_is_the_attention_form(reference):
    q, k, v, log_g = _parts(4, 40)
    ys, _, _ = _recurrence(q, k, v, log_g)
    want = _attention_form(reference, q, k, v, log_g)[0]
    numpy.testing.assert_allclose(
        numpy.concatenate([numpy.asarray(y) for y in ys]), want, **CLOSE)


@pytest.mark.parametrize("chunk", [1024, 16])
def test_the_state_after_a_row_is_the_state_at_its_true_length(
        monkeypatch, chunk):
    """Right-padded rows of unequal length in one group: each row's
    state is what the recurrence holds after its own last position,
    whatever the padding holds."""
    monkeypatch.setattr(retention, "CHUNK", chunk)
    q, k, v, log_g = _parts(5, 32, batch=3)
    lengths = numpy.asarray([32, 7, 19])
    live = jnp.asarray(numpy.arange(32)[None] < lengths[:, None])
    got = retention.state_after(k, v, log_g, live)
    for row, length in enumerate(lengths):
        _, held, norm = _recurrence(
            *(a[row:row + 1] for a in (q, k, v, log_g)), steps=length)
        numpy.testing.assert_allclose(
            numpy.asarray(got["S"][row]), numpy.asarray(held[0]), **CLOSE)
        numpy.testing.assert_allclose(
            numpy.asarray(got["z"][row]), numpy.asarray(norm[0]), **CLOSE)


def test_an_idle_lane_keeps_its_state_bit_for_bit():
    q, k, v, log_g = _parts(6, 1, batch=3)
    rng = numpy.random.RandomState(0)
    d = q.shape[-1]
    held = jnp.asarray(rng.randn(3, 2, d, retention.features(d))
                       .astype(numpy.float32))
    norm = jnp.asarray(rng.rand(3, 2, retention.features(d))
                       .astype(numpy.float32))
    active = jnp.asarray([True, False, True])
    _, new_held, new_norm = retention.step(
        q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], held, norm, active)
    assert numpy.array_equal(numpy.asarray(new_held[1]),
                             numpy.asarray(held[1]))
    assert numpy.array_equal(numpy.asarray(new_norm[1]),
                             numpy.asarray(norm[1]))
    assert not numpy.array_equal(numpy.asarray(new_held[0]),
                                 numpy.asarray(held[0]))


def _not_close(got, want):
    """The comparison the tests above pass, failed: beyond CLOSE."""
    return not numpy.allclose(got, want, **CLOSE)


def test_planted_faults_read_not_correct(reference, monkeypatch):
    """Each fault alone puts the recurrence's answers (or the kept
    state) beyond the tolerance the true one meets."""
    q, k, v, log_g = _parts(7, 24)
    want = _attention_form(reference, q, k, v, log_g)[0]

    def answers(**fault):
        ys, _, _ = _recurrence(q, k, v, log_g, **fault)
        return numpy.concatenate([numpy.asarray(y) for y in ys])

    assert not _not_close(answers(), want)
    # the gate applied to the new term too
    assert _not_close(answers(gate_new=True), want)
    # z not decayed: the normaliser outgrows the state
    _, _, norm = _recurrence(q, k, v, log_g)
    _, _, kept = _recurrence(q, k, v, log_g, z_kept=True)
    assert _not_close(numpy.asarray(kept), numpy.asarray(norm))
    # the sqrt(2) left off the rows that hold a pair once
    with monkeypatch.context() as patch:
        patch.setattr(retention, "_weights", lambda d: numpy.ones(
            (d // 2 + 1, 1), numpy.float32))
        jax.clear_caches()
        assert _not_close(answers(), want)
    jax.clear_caches()
    # the state taken at the bucket's end, not the row's
    live = jnp.asarray(numpy.arange(24)[None] < 9)
    true = retention.state_after(k, v, log_g, live)
    end = retention.state_after(k, v, log_g)
    assert _not_close(numpy.asarray(end["S"]), numpy.asarray(true["S"]))


def test_a_bfloat16_state_over_512_steps_reads_not_correct(reference):
    """The recurrence sums thousands of terms: a state kept in
    bfloat16 loses them (an addend below 2^-8 of the sum is dropped),
    float32 does not."""
    q, k, v, log_g = _parts(8, 512, heads=2, groups=1, d=8)
    log_g = jnp.full_like(log_g, -1.0 / 400.0)     # a long memory
    want = _attention_form(reference, q, k, v, log_g)[0][-64:]

    def last(dtype):
        ys, _, _ = _recurrence(q, k, v, log_g, dtype=dtype)
        return numpy.concatenate([numpy.asarray(y) for y in ys[-64:]])

    assert not _not_close(last(jnp.float32), want)
    assert _not_close(last(jnp.bfloat16), want)


# -- the kind through the model seam ------------------------------------------

def _state(params, table, heads):
    return decode.init_slot_state(
        len(params["blocks"]), SLOTS, MAX_LEN, heads,
        table.shape[1] // heads, table.shape[0], dtype=table.dtype,
        arch=blocks.arch_of(params))


def _admit(params, table, heads, state, slots, prompts):
    rows = list(zip(slots, prompts))
    size = 1
    while size < len(rows):
        size *= 2
    rows += rows[:1] * (size - len(rows))
    padded = numpy.zeros((len(rows), BUCKET), numpy.int32)
    for j, (_, prompt) in enumerate(rows):
        padded[j, :len(prompt)] = prompt
    return decode.slot_admit_many(
        params, table, heads, state,
        jnp.asarray([slot for slot, _ in rows], jnp.int32),
        table[jnp.asarray(padded)],
        jax.random.split(jax.random.key(0), len(rows)),
        jnp.asarray([len(prompt) for _, prompt in rows], jnp.int32))


def _prompts(config, lengths, seed=1):
    rng = numpy.random.RandomState(seed)
    return [rng.randint(0, config["vocab_size"], n).tolist()
            for n in lengths]


def test_the_state_holds_no_row_a_position(config, model):
    params, table = model
    state = _state(params, table, config["n_head"])
    assert decode._kv_names(state) == []
    d = config["head_dim"]
    wide = retention.features(d)
    held = state[decode.FIXED]
    assert [leaf.shape for leaf in held["S"]] == [
        (SLOTS, config["num_key_value_heads"], d, wide)] * 2
    assert {leaf.dtype for leaf in held["S"] + held["z"]} \
        == {jnp.dtype(jnp.float32)}
    holds = decode.slot_holds(params, state)
    assert holds["block_kinds"] == {"ret": 2}
    assert holds["slot_row_bytes_per_position"] == 0
    assert holds["slot_fixed_state_bytes"] \
        == 2 * config["num_key_value_heads"] * wide * (d + 1) * 4
    assert decode.slot_attend_path(params, state) == "xla"
    assert decode.slot_state_path(params, state) == "xla"
    assert decode.slot_write_path(state, 8) is None
    assert decode.decide_slot_formats(params, table, config["n_head"],
                                      state, 8, 64) == {}
    assert set(decode.slot_layout_facts(state)) == {"state_device_bytes"}


def test_prefill_then_24_steps_are_the_reference_s_full_forward(
        reference, config, model):
    """Right-padded rows of 1, 5, 11 and bucket-length tokens admitted
    as one group, then 24 single steps through the state: at every
    step the slot's logits are the reference's over the whole sequence
    so far (the attention form, no state)."""
    params, table = model
    heads = config["n_head"]
    prompts = _prompts(config, (1, 5, 11, BUCKET))
    state = _admit(params, table, heads, _state(params, table, heads),
                   range(4), prompts)
    active = jnp.ones((SLOTS,), bool)
    logits, tokens = [], []
    for _ in range(24):
        logits.append(numpy.asarray(state["logits"]))
        state, emitted = decode.slot_step(params, table, heads, state,
                                          active)
        tokens.append(numpy.asarray(emitted))
    logits, tokens = numpy.stack(logits, 1), numpy.stack(tokens, 1)
    for lane, prompt in enumerate(prompts):
        want = numpy.asarray(reference.logits_after(
            dict(config, serving=dict(config["serving"], n_tokens=24)),
            params, table, prompt, tokens[lane].tolist()))
        numpy.testing.assert_allclose(logits[lane], want, **CLOSE)


def test_a_chunk_leaves_an_idle_lane_s_state_untouched(config, model):
    params, table = model
    heads = config["n_head"]
    prompts = _prompts(config, (6, 9))
    state = _admit(params, table, heads, _state(params, table, heads),
                   (0, 2), prompts)
    before = jax.tree.map(numpy.asarray, state[decode.FIXED])
    active = jnp.asarray([True, False, False, False])
    state, _ = decode.slot_step_many(params, table, heads, state,
                                     active, 8)
    after = jax.tree.map(numpy.asarray, state[decode.FIXED])
    for name in ("S", "z"):
        for was, now in zip(before[name], after[name]):
            assert numpy.array_equal(was[1:], now[1:])
            assert not numpy.array_equal(was[0], now[0])
    assert numpy.asarray(state["lengths"]).tolist() == [14, 0, 9, 0]


# -- a model with no positional leaf through ContinuousDecoder ----------------

def _decoder(model, config, **kwargs):
    from veles_tpu.serving import ContinuousDecoder

    params, table = model
    return ContinuousDecoder(params, table, config["n_head"], slots=2,
                             max_len=MAX_LEN, n_tokens=6, **kwargs)


def test_admit_chunk_collect_retire_and_readmit_into_the_same_slot(
        reference, config, model):
    """Five requests through two slots, so that every slot is taken
    again: each answer is the reference's own greedy continuation, so
    nothing of a slot's old state stayed. One step program whatever
    the slots hold."""
    params, table = model
    decoder = _decoder(model, config)
    step_many = decode.slot_fns(decoder.state)[2].__wrapped__
    programs = step_many._cache_size()
    prompts = _prompts(config, (3, 17, 1, 9, 30), seed=2)
    rids = [decoder.submit(numpy.asarray(p), 6) for p in prompts]
    decoder.drain_pipelined(4)
    assert not decoder.busy and sorted(decoder._free) == [0, 1]
    for rid, prompt in zip(rids, prompts):
        gaps = reference.served_gaps(config, params, table, prompt,
                                     decoder.results[rid])
        assert len(decoder.results[rid]) == 6 and gaps.max() <= GAP
    assert decoder._attended_span(4) == 0
    assert decoder.dispatch_counts["chunk"] >= 4
    assert decoder.slot_holds["block_kinds"] == {"ret": 2}
    assert decoder.slot_holds["slot_row_bytes_per_position"] == 0
    assert decoder.attend_paths == {
        "kernel": 0, "xla": decoder.dispatch_counts["chunk"]}
    assert decoder.state_paths == decoder.attend_paths
    # no block to write: no books, and a dispatch's span says none
    assert decoder.write_paths is None
    assert "block_write_path" not in decoder._book_attend_path(4)
    assert decoder.kv_layout.keys() == {"state_device_bytes"}
    assert step_many._cache_size() == programs + 1


@pytest.mark.parametrize("kwargs, tier, lacks", [
    (dict(paged=True), r"paged=True \(the page pool\)",
     "keeps no row a position"),
    (dict(paged=True, prefix_cache="a cache"), r"paged=True",
     "no table indexes"),
    (dict(quantize="int8"), "quantize='int8'",
     "a float32 state that a recurrence sums into"),
    (dict(quantize="int8-kv"), "quantize='int8-kv'",
     "int8 rows cannot hold"),
    (dict(mesh="a mesh"), r"mesh= \(tensor-parallel serving\)",
     "would shard over K/V heads"),
])
def test_the_tiers_built_on_gpt2_s_leaves_refuse_the_kind_by_name(
        config, model, kwargs, tier, lacks):
    with pytest.raises(ValueError) as refused:
        _decoder(model, config, **kwargs)
    text = str(refused.value)
    import re

    assert re.search(tier, text), text
    assert "attention='ret'" in text and "for kind 'ret'" in text, text
    assert lacks in text, text


def test_the_prefix_cache_s_refusal_says_what_a_snapshot_would_take(
        config, model):
    with pytest.raises(ValueError) as refused:
        blocks.require_gpt2(model[0], "prefix_cache=", tier="prefix")
    assert "would have to be snapshot" in str(refused.value)


# -- the state's kernel, interpreted ------------------------------------------

def test_the_rule_takes_the_kernel_only_where_it_fits(monkeypatch):
    from jax.sharding import SingleDeviceSharding

    here = SingleDeviceSharding(jax.devices()[0])
    leaf = jax.ShapeDtypeStruct((2, 1, 128, retention.features(128)),
                                jnp.float32)
    assert retention.state_path(leaf, here) == "xla"       # the CPU
    monkeypatch.setattr(retention, "on_tpu", lambda: True)
    monkeypatch.setattr(retention, "device_kind", lambda: "TPU v5 lite")
    assert retention.state_path(leaf, here) == "kernel"
    assert retention.state_path(leaf, None) == "xla"       # nobody knows
    bf16 = jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16)
    assert retention.state_path(bf16, here) == "xla"
    small = jax.ShapeDtypeStruct((2, 1, 16, retention.features(16)),
                                 jnp.float32)
    assert retention.state_path(small, here) == "xla"
    monkeypatch.setattr(retention, "device_kind", lambda: "TPU v9")
    assert retention.state_path(leaf, here) == "xla"       # VMEM unknown


def test_the_kernel_gives_the_plain_step_s_numbers(monkeypatch):
    """``retention_step`` interpreted on the CPU at one lane tile of
    ``head_dim``: the state, the normaliser and the answers of
    ``jax.numpy``'s step, an idle lane's state bit for bit."""
    from jax.sharding import SingleDeviceSharding

    here = SingleDeviceSharding(jax.devices()[0])
    q, k, v, log_g = _parts(9, 1, heads=3, groups=1, d=128, batch=2)
    rng = numpy.random.RandomState(1)
    wide = retention.features(128)
    held = jnp.asarray(rng.randn(2, 1, 128, wide).astype(numpy.float32))
    norm = jnp.asarray(rng.rand(2, 1, wide).astype(numpy.float32) + 1.0)
    active = jnp.asarray([True, False])
    args = (q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], held, norm, active)
    want = retention.step(*args, sharding=here)
    monkeypatch.setattr(retention, "on_tpu", lambda: True)
    monkeypatch.setattr(retention, "device_kind", lambda: "TPU v5 lite")
    assert retention.state_path(held, here) == "kernel"
    got = retention.step(*args, sharding=here)
    for mine, plain in zip(got, want):
        numpy.testing.assert_allclose(numpy.asarray(mine),
                                      numpy.asarray(plain),
                                      rtol=2e-5, atol=2e-5)
    assert numpy.array_equal(numpy.asarray(got[1][1]),
                             numpy.asarray(held[1]))
