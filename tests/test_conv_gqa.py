"""The model seam's third instance against its plain reference: a kind
for each block.

LFM2-8B-A1B's blocks (gated short convolutions with a fixed state a
slot, beside grouped-query attention with a norm on each head's q and
k and RoPE; routed experts without a shared one: ``parallel/blocks.py``,
``parallel/decode.py``, ``ops/moe.py``) at the benchmark
configuration's rehearsal sizes, on seeded weights from the
reference's own ``init_params``, widened to float32 so that what the
comparisons see is the order of the arithmetic and no rounding of
operands: logits agree to ``LOGITS``.
"""

import importlib.util
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import moe
from veles_tpu.parallel import blocks, decode
from veles_tpu.parallel.transformer_step import (
    _forward, build_transformer_train_step)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS = dict(rtol=2e-4, atol=2e-4)
#: the most a served token's reference logit may lie below the best
GAP = 2e-4
SLOTS, MAX_LEN, BUCKET = 8, 64, 16


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("benchmark/references/lfm2-8b-a1b.py", "lfm2_reference")


def _config(**over):
    with open(os.path.join(
            ROOT, "benchmark/configs/lfm2-8b-a1b.json")) as fin:
        config = json.load(fin)
    small = dict(config["rehearsal"])
    config["serving"] = dict(config["serving"], **small.pop("serving"))
    config.update(small)
    config.update(over)
    return config


@pytest.fixture(scope="module")
def config():
    return _config()


@pytest.fixture(scope="module")
def model(reference, config):
    params, table = reference.init_params(5, config)
    return jax.tree.map(lambda a: a.astype(jnp.float32), (params, table))


def _state(params, table, heads):
    return decode.init_slot_state(
        len(params["blocks"]), SLOTS, MAX_LEN, heads,
        table.shape[1] // heads, table.shape[0], dtype=table.dtype,
        arch=blocks.arch_of(params))


def _admit(params, table, heads, state, slots, prompts):
    """One padded group, as the decoder makes it: every row
    right-padded to the bucket, the group padded to a power of two
    with duplicates of its first row."""
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


def _serve(params, table, heads, prompts, steps):
    """``prompts`` admitted as one padded group into slots 0.., then
    ``steps`` single decode steps: ``(logits (lanes, steps, V), tokens
    (lanes, steps), state)``."""
    state = _admit(params, table, heads, _state(params, table, heads),
                   range(len(prompts)), prompts)
    active = jnp.arange(SLOTS) < len(prompts)
    logits, tokens = [], []
    for _ in range(steps):
        logits.append(numpy.asarray(state["logits"][:len(prompts)]))
        state, emitted = decode.slot_step(params, table, heads, state,
                                          active, span=MAX_LEN)
        tokens.append(numpy.asarray(emitted[:len(prompts)]))
    return numpy.stack(logits, 1), numpy.stack(tokens, 1), state


def _prompts(config, lengths, seed=1):
    rng = numpy.random.RandomState(seed)
    return [rng.randint(0, config["vocab_size"], n).tolist()
            for n in lengths]


def _agree(reference, config, model, prompts, logits, tokens):
    params, table = model
    for row, prompt in enumerate(prompts):
        want = reference.logits_after(config, params, table, prompt,
                                      tokens[row].tolist())
        numpy.testing.assert_allclose(logits[row], numpy.asarray(want),
                                      **LOGITS)


def test_the_plain_forward_runs_a_kind_per_block(reference, config, model):
    """(a) the full forward of ``transformer_step`` (one definition
    with prefill and decode) against the reference at every position,
    the head read from the embedding table."""
    params, table = model
    tokens = _prompts(config, [24], seed=3)[0]
    got = _forward(params, table[jnp.asarray(tokens)][None],
                   config["n_head"], 1, "ulysses", embed_table=table)[0]
    want = reference.logits_after(config, params, table, tokens[:1],
                                  tokens[1:] + [0])
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(want),
                                  **LOGITS)


def test_admission_then_decode_through_both_kinds_of_state(
        reference, config, model):
    """(b) one padded group whose prompts are 1, 2 and 3 tokens (shorter
    than the convolution reaches back), the bucket's full length and
    one short of it: the conv state is taken at each row's TRUE length,
    the K/V rows to the bucket's end, and every decode step after
    agrees with the reference's whole-sequence logits."""
    prompts = _prompts(config, [1, 2, 3, BUCKET, BUCKET - 1])
    logits, tokens, state = _serve(*model, config["n_head"], prompts, 6)
    _agree(reference, config, model, prompts, logits, tokens)
    # the slot's two kinds of state, side by side
    kinds = blocks.block_kinds(model[0]["arch"], len(model[0]["blocks"]))
    conv = sum(kind is blocks.ShortConv for kind in kinds)
    assert len(state[decode.FIXED]["conv"]) == conv == 3
    assert len(state["k"]) == len(state["v"]) == len(kinds) - conv == 2
    e, kv = config["hidden_size"], config["num_key_value_heads"]
    assert state[decode.FIXED]["conv"][0].shape == (SLOTS, 2 * e)
    assert state["k"][0].shape == (
        SLOTS, kv * e // config["n_head"], MAX_LEN)
    assert decode._kv_names(state) == ["k", "v"]


def test_a_chunk_is_its_steps(reference, config, model):
    """The state rides in the chunk's carry and is written back once:
    four steps as one dispatch give the tokens and the state of four
    dispatches of one."""
    params, table = model
    heads = config["n_head"]
    prompts = _prompts(config, [5, 11, 2], seed=4)
    _, tokens, stepped = _serve(params, table, heads, prompts, 4)
    state = _admit(params, table, heads, _state(params, table, heads),
                   range(3), prompts)
    state, emitted = decode.slot_step_many(
        params, table, heads, state, jnp.arange(SLOTS) < 3, 4,
        span=MAX_LEN)
    emitted, load = decode.split_emitted(emitted)
    numpy.testing.assert_array_equal(numpy.asarray(emitted)[:, :3].T,
                                     tokens)
    # the expert blocks' load of every step: 3 live lanes x top_k
    assert load.shape == (4, 4, config["num_experts"])
    assert (numpy.asarray(load).sum(-1)
            == 3 * config["num_experts_per_tok"]).all()
    for got, want in zip(state[decode.FIXED]["conv"],
                         stepped[decode.FIXED]["conv"]):
        numpy.testing.assert_allclose(numpy.asarray(got[:3]),
                                      numpy.asarray(want[:3]), **LOGITS)


def test_a_readmitted_slot_holds_nothing_of_its_former_occupant(
        reference, config, model):
    """(c) a slot retired after a long sequence and admitted again with
    a prompt of one token: its conv state is that of the one token
    (zeros before it), and its stream is the reference's."""
    params, table = model
    heads = config["n_head"]
    first = _prompts(config, [BUCKET, 9], seed=6)
    _, _, state = _serve(params, table, heads, first, 5)
    second = _prompts(config, [1], seed=7)
    state = _admit(params, table, heads, state, [0], second)
    e = config["hidden_size"]
    for leaf in state[decode.FIXED]["conv"]:
        # the older of the two gated inputs does not exist yet
        assert not numpy.asarray(leaf[0, :e]).any()
        assert numpy.asarray(leaf[0, e:]).any()
    logits, tokens = [], []
    active = jnp.arange(SLOTS) < 1
    for _ in range(5):
        logits.append(numpy.asarray(state["logits"][:1]))
        state, emitted = decode.slot_step(params, table, heads, state,
                                          active, span=MAX_LEN)
        tokens.append(numpy.asarray(emitted[:1]))
    _agree(reference, config, model, second, numpy.stack(logits, 1),
           numpy.stack(tokens, 1))


def test_an_idle_lane_keeps_its_state_through_a_chunk(
        reference, config, model):
    """(c) lane 1 sits out a chunk of four steps: its conv state, its
    length and its logits are what they were, and when it decodes
    afterwards its stream is the reference's."""
    params, table = model
    heads = config["n_head"]
    prompts = _prompts(config, [7, 12], seed=8)
    state = _admit(params, table, heads, _state(params, table, heads),
                   range(2), prompts)
    before = [numpy.asarray(leaf[1])
              for leaf in state[decode.FIXED]["conv"]]
    state, _ = decode.slot_step_many(
        params, table, heads, state, jnp.asarray([True] + [False] * 7),
        4, span=MAX_LEN)
    for was, leaf in zip(before, state[decode.FIXED]["conv"]):
        numpy.testing.assert_array_equal(numpy.asarray(leaf[1]), was)
    assert int(state["lengths"][1]) == 12
    logits, tokens = [], []
    only = jnp.asarray([False, True] + [False] * 6)
    for _ in range(4):
        logits.append(numpy.asarray(state["logits"][1:2]))
        state, emitted = decode.slot_step(params, table, heads, state,
                                          only, span=MAX_LEN)
        tokens.append(numpy.asarray(emitted[1:2]))
    _agree(reference, config, model, prompts[1:], numpy.stack(logits, 1),
           numpy.stack(tokens, 1))


def _wrong_heads(arch, blk, q, read, staged, mask, mask_staged,
                 real=blocks.Grouped.attend_cached):
    """Query head ``i`` sent to K/V head ``i % kv_heads``."""
    slots, _, heads, dim = q.shape
    groups = arch.kv_heads

    def turned(x, a, b):
        return jnp.swapaxes(x.reshape(slots, 1, a, b, dim), 2, 3) \
            .reshape(slots, 1, heads, dim)

    att = real(arch, blk, turned(q, heads // groups, groups), read,
               staged, mask, mask_staged)
    return turned(att.reshape(q.shape), groups, heads // groups) \
        .reshape(slots, 1, -1)


@pytest.mark.parametrize("fault", [
    "state_at_the_buckets_end", "a_tap_left_out", "no_norm_on_q_and_k",
    "wrong_kv_head"])
def test_a_planted_fault_reads_not_correct(reference, config, model,
                                           monkeypatch, fault):
    """(d) a program with one piece of the mathematics wrong: its
    logits leave the reference's, and what the harness compares
    (``served_gaps``) reads a hundred times what the honest program
    may read here in float32 (``GAP``). (At these toy widths, 12 tokens
    an answer, that is not yet the configuration's limit, which is set
    on the chip at the cell's own size.)"""
    params, table = model
    if fault == "state_at_the_buckets_end":
        keep = blocks.ShortConv.keep
        monkeypatch.setattr(
            blocks.ShortConv, "keep",
            staticmethod(lambda arch, rows, live: keep(arch, rows, None)))
    elif fault == "a_tap_left_out":
        taps = blocks.ShortConv._taps
        monkeypatch.setattr(
            blocks.ShortConv, "_taps",
            staticmethod(lambda blk, inputs: taps(
                dict(blk, conv_w=blk["conv_w"].at[0].set(0.0)), inputs)))
    elif fault == "no_norm_on_q_and_k":
        norm = blocks.rms_norm
        monkeypatch.setattr(
            blocks, "rms_norm", lambda x, w, eps:
            x if x.ndim == 4 else norm(x, w, eps))
    else:
        monkeypatch.setattr(blocks.Grouped, "attend_cached",
                            staticmethod(_wrong_heads))
    prompts = _prompts(config, [3, 9, BUCKET - 2, 12], seed=9)
    # the patched sublayers must be traced: JAX keeps a function's
    # trace whatever jit object asks for it
    jax.clear_caches()
    try:
        logits, tokens, _ = _serve(params, table, config["n_head"],
                                   prompts, 12)
    finally:
        jax.clear_caches()
    with pytest.raises(AssertionError):
        _agree(reference, config, model, prompts, logits, tokens)
    widest = max(float(reference.served_gaps(
        config, params, table, prompt, tokens[row].tolist()).max())
        for row, prompt in enumerate(prompts))
    assert widest > 100 * GAP, widest


def test_the_shares_of_32_experts_top_4_add_up_without_a_shared_one(
        reference):
    """(e) the layer at the published count (32 experts, 4 a token, no
    shared expert, the 1e-6 in the normalising sum) run with ``held`` =
    each quarter of the experts sums to the whole layer, which is the
    loop over every expert."""
    config = _config(num_experts=32, num_experts_per_tok=4)
    params, _ = reference.init_params(11, config)
    arch = params["arch"]
    blk = jax.tree.map(lambda a: a.astype(jnp.float32),
                       params["blocks"][1])
    assert "shared" not in blk and arch.route_eps == 1e-6
    h = jnp.asarray(numpy.random.RandomState(7).randn(
        29, config["hidden_size"]), jnp.float32)
    whole, load = moe.expert_layer(h, blk, arch.top_k, arch.route_scale,
                                   eps=arch.route_eps)
    chosen, weights = moe.route(h, blk["router"], blk["router_bias"],
                                arch.top_k, arch.route_scale,
                                arch.route_eps)
    numpy.testing.assert_allclose(
        numpy.asarray(weights.sum(-1)),
        numpy.asarray(1 / (1 + 1e-6 / jnp.take_along_axis(
            jax.nn.sigmoid(h @ blk["router"]), chosen, -1).sum(-1))),
        rtol=1e-6)
    total, loads = jnp.zeros_like(h), []
    for first in range(0, 32, 8):
        held = jax.tree.map(lambda w: w[first:first + 8], blk["experts"])
        part, part_load = moe.routed_experts(
            h, chosen, weights, held, held=(first, 8))
        total = total + part
        loads.append(numpy.asarray(part_load))
    numpy.testing.assert_allclose(numpy.asarray(total),
                                  numpy.asarray(whole),
                                  rtol=1e-4, atol=1e-5)
    numpy.testing.assert_array_equal(numpy.concatenate(loads),
                                     numpy.asarray(load))
    assert int(load.sum()) == 29 * 4
    loop = jnp.zeros_like(h)
    for e in range(32):
        share = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        loop = loop + share[:, None] * moe.swiglu(
            h, jax.tree.map(lambda w: w[e], blk["experts"]))
    numpy.testing.assert_allclose(numpy.asarray(whole),
                                  numpy.asarray(loop),
                                  rtol=1e-4, atol=1e-5)


def test_grouped_heads_attend_their_own_kv_head_as_it_lies(config, model):
    """The cached form against the prompt's on the same rows: the last
    position's output of ``attend_prompt`` against ``attend_cached``
    over the rows before it (the window) and its own (the staged
    column), with no K/V row repeated."""
    params, _ = model
    arch, blk, heads = params["arch"], params["blocks"][1], \
        config["n_head"]
    x = jnp.asarray(numpy.random.RandomState(4).randn(
        3, 17, config["hidden_size"]), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(17), (3, 17))
    q, rows = blocks.Grouped.project(arch, blk, x, heads, positions)
    assert rows["k"].shape == (3, 17, config["num_key_value_heads"], 16)
    want = blocks.Grouped.attend_prompt(arch, blk, q, rows)[:, -1:]
    state = {"k": (jnp.zeros((3, 32, 1), jnp.float32),)}
    columns = blocks.Grouped.columns(state, rows)
    got = blocks.Grouped.attend_cached(
        arch, blk, q[:, -1:],
        {name: leaf[..., :16] for name, leaf in columns.items()},
        {name: leaf[..., 16:] for name, leaf in columns.items()},
        jnp.ones((3, 1, 1, 16), bool), jnp.ones((3, 1, 1, 1), bool))
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(want),
                                  rtol=1e-4, atol=1e-5)


def test_the_decoder_serves_it_and_says_what_a_slot_holds(
        reference, config, model):
    """Through ``ContinuousDecoder``: admission groups, chunks and the
    lag-1 pipeline, slots retired and taken again; every answered token
    is the reference's first, and the books say which kinds the blocks
    are and what a slot holds of each kind of state."""
    from veles_tpu.serving import ContinuousDecoder

    params, table = model
    decoder = ContinuousDecoder(params, table, config["n_head"],
                                slots=4, max_len=MAX_LEN, n_tokens=7)
    prompts = _prompts(config, [1, 20, 2, 33, 11, 3, 16, 15, 40], seed=2)
    rids = [decoder.submit(prompt) for prompt in prompts]
    decoder.drain_pipelined(4)
    for rid, prompt in zip(rids, prompts):
        gaps = reference.served_gaps(config, params, table, prompt,
                                     decoder.results[rid])
        assert gaps.max() <= GAP, gaps
    e, kv_row = config["hidden_size"], 2 * 16
    assert decoder.slot_holds == {
        "block_kinds": {"conv": 3, "gqa": 2},
        "slot_row_bytes_per_position": 2 * 2 * kv_row * 4,
        "slot_fixed_state_bytes": 3 * 2 * e * 4}
    assert decoder.attend_paths["kernel"] == 0 < decoder.attend_paths["xla"]
    assert decoder.moe_load["assignments"].shape == (
        4, config["num_experts"])


def test_healthz_says_the_block_kinds(config, model):
    from veles_tpu.serving import GenerateAPI

    params, table = model
    api = GenerateAPI(params, table, config["n_head"], slots=2,
                      max_len=MAX_LEN, n_tokens=4, port=0)
    try:
        counters = api.health.snapshot()["counters"]
    finally:
        api.stop()
    assert counters["block_kinds"] == {"conv": 3, "gqa": 2}
    assert counters["slot_fixed_state_bytes"] == 3 * 2 * 64 * 4
    assert counters["slot_row_bytes_per_position"] == 2 * 2 * 32 * 4


def test_one_kind_for_every_block_is_a_model_of_one_kind():
    """``Arch(attention="mla")`` is ``Arch(layers="mla")``; a tree
    without an architecture is GPT-2's block throughout; a kind per
    block has to name every block, by a name that exists."""
    assert blocks.Arch(attention="mla") == blocks.Arch(layers="mla")
    assert hash(blocks.Arch(attention="mla")) \
        == hash(blocks.Arch(layers="mla"))
    assert blocks.Arch() == blocks.GPT2 and blocks.GPT2.layers == "mha"
    assert blocks.block_kinds(blocks.GPT2, 3) == (blocks.FusedQKV,) * 3
    mixed = blocks.Arch(layers=["conv", "gqa", "conv"], kv_heads=2)
    assert mixed.layers == ("conv", "gqa", "conv")
    kinds = blocks.block_kinds(mixed, 3)
    assert kinds == (blocks.ShortConv, blocks.Grouped, blocks.ShortConv)
    assert blocks.leaf_ordinals(kinds) == (0, 0, 1)
    assert blocks.leaf_ordinals(
        (blocks.FusedQKV, blocks.Grouped, blocks.Latent)) == (0, 1, 0)
    with pytest.raises(ValueError, match="for 3 blocks and has 2"):
        blocks.block_kinds(mixed, 2)
    with pytest.raises(ValueError, match="no block kind 'lin'"):
        blocks.block_kinds(blocks.Arch(layers=("lin",)), 1)


@pytest.mark.parametrize("tier, named, lacks", [
    (dict(paged=True), "paged=True", "no fixed state beside them"),
    (dict(quantize="int8"), "quantize='int8'", "quantize_params"),
    (dict(quantize="int8-kv"), "quantize='int8-kv'", "int8 cache"),
    (dict(mesh=object()), "mesh=", "slot_state_specs"),
    (dict(aot=object()), "aot=", "one k/v slab"),
    (dict(prefix_cache=object()), "prefix_cache=",
     "a fixed state would have to be snapshot"),
])
def test_a_tier_built_on_gpt2s_leaves_refuses_a_kind_per_block(
        config, model, tier, named, lacks):
    """Each by name, saying what it lacks, before anything is placed
    on the device."""
    from veles_tpu.serving import ContinuousDecoder

    params, table = model
    with pytest.raises(ValueError) as refused:
        ContinuousDecoder(params, table, config["n_head"], slots=2,
                          max_len=32, **tier)
    said = str(refused.value)
    assert named in said and lacks in said
    assert "layers=(3 x 'conv', 2 x 'gqa')" in said


def test_generate_export_and_the_train_step_refuse_it(config, model):
    params, table = model
    with pytest.raises(ValueError, match="generate"):
        decode.generate(params, table, jnp.zeros((1, 4), jnp.int32),
                        config["n_head"], 2)
    with pytest.raises(ValueError, match="train step"):
        build_transformer_train_step(config["n_head"])(
            params, jnp.zeros((1, 4, config["hidden_size"])),
            jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="page pool"):
        decode.init_slot_state(5, 2, 32, 4, 16, 211, paged=True,
                               arch=params["arch"])
