"""The model seam's second instance against its plain reference.

JoyAI-LLM-Flash's block (latent attention with RoPE, routed experts
top-k with a shared expert, RMSNorm, SwiGLU: ``parallel/blocks.py``,
``ops/moe.py``) at the benchmark configuration's rehearsal sizes, on
seeded weights from the reference's own ``init_params``, widened to
float32 so that what the comparisons see is the order of the
arithmetic and no rounding of operands: logits agree to ``LOGITS``
(the cached path contracts over the latent space where the reference
expands it, and sums the experts a token at a time where the
reference sums them a group at a time; at these sizes that moves a
logit of size ~1 by a few 1e-6).
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
    _forward, build_transformer_train_step, init_transformer_params)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOGITS = dict(rtol=2e-4, atol=2e-4)
SLOTS, MAX_LEN = 4, 128


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, path))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("benchmark/references/joyai-llm-flash.py",
                 "joyai_reference")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(
            ROOT, "benchmark/configs/joyai-llm-flash.json")) as fin:
        config = json.load(fin)
    small = dict(config["rehearsal"])
    config["serving"] = dict(config["serving"], **small.pop("serving"))
    config.update(small)
    return config


@pytest.fixture(scope="module")
def model(reference, config):
    params, table = reference.init_params(5, config)
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), (params, table))
    return wide


def _serve(params, table, heads, prompts, steps):
    """Prefill ``prompts`` into slots 0.. of a fresh slab and decode
    ``steps`` greedy tokens each, one ``slot_step`` at a time: the
    logits every answered token was drawn from, and the tokens."""
    arch = blocks.arch_of(params)
    state = decode.init_slot_state(
        len(params["blocks"]), SLOTS, MAX_LEN, heads,
        table.shape[1] // heads, table.shape[0], dtype=table.dtype,
        arch=arch)
    for slot, prompt in enumerate(prompts):
        state = decode.slot_admit(
            params, table, heads, state, slot,
            table[jnp.asarray(prompt)][None])
    active = jnp.arange(SLOTS) < len(prompts)
    logits, tokens = [], []
    for _ in range(steps):
        logits.append(numpy.asarray(state["logits"][:len(prompts)]))
        state, emitted = decode.slot_step(params, table, heads, state,
                                          active, span=MAX_LEN)
        tokens.append(numpy.asarray(emitted[:len(prompts)]))
    return numpy.stack(logits, 1), numpy.stack(tokens, 1)


def test_prefill_then_decode_through_the_slab_matches_the_reference(
        reference, config, model):
    """(a) at every served position: the prompt through the expanded
    attention into the latent slab, then absorbed decode steps out of
    it, against the reference's whole-sequence forward."""
    params, table = model
    rng = numpy.random.RandomState(1)
    prompts = [rng.randint(0, config["vocab_size"], n).tolist()
               for n in (9, 37, 70)]
    logits, tokens = _serve(params, table, config["n_head"], prompts, 6)
    for row, prompt in enumerate(prompts):
        want = reference.logits_after(config, params, table, prompt,
                                      tokens[row].tolist())
        numpy.testing.assert_allclose(logits[row], numpy.asarray(want),
                                      **LOGITS)


def test_the_decoder_serves_it_and_books_the_experts_load(
        reference, config, model):
    """(a) through ``ContinuousDecoder``: admission groups, chunks and
    the lag-1 pipeline; every answered token is the reference's first
    (a gap over ``LOGITS``'s size would be another token), and the
    chunk's expert load reaches the books."""
    from veles_tpu.serving import ContinuousDecoder

    params, table = model
    rng = numpy.random.RandomState(2)
    decoder = ContinuousDecoder(params, table, config["n_head"],
                                slots=SLOTS, max_len=MAX_LEN, n_tokens=7)
    prompts = [rng.randint(0, config["vocab_size"], n).tolist()
               for n in (5, 20, 21, 60, 11, 33)]
    rids = [decoder.submit(prompt) for prompt in prompts]
    decoder.drain_pipelined(4)
    for rid, prompt in zip(rids, prompts):
        gaps = reference.served_gaps(config, params, table, prompt,
                                     decoder.results[rid])
        assert gaps.max() <= 2e-4, gaps
    books = decoder.moe_load
    top_k, experts = config["num_experts_per_tok"], \
        config["n_routed_experts"]
    assert books["assignments"].shape == (2, experts)
    for lanes, (steps, assigned, touched) in books["by_lanes"].items():
        # no token dropped: every live slot's token has its top_k
        assert assigned == steps * lanes * top_k
        assert 0 < touched <= steps * min(experts, lanes * top_k)
    assert decoder.moe_load_max_over_mean() >= 1.0


def test_the_plain_forward_runs_the_same_block(reference, config, model):
    """The full forward of ``transformer_step`` (one definition with
    prefill and decode) against the reference at every position."""
    params, table = model
    tokens = numpy.random.RandomState(3).randint(
        0, config["vocab_size"], 24).tolist()
    got = _forward(params, table[jnp.asarray(tokens)][None],
                   config["n_head"], 1, "ulysses")[0]
    want = reference.logits_after(config, params, table, tokens[:1],
                                  tokens[1:] + [0])
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(want),
                                  **LOGITS)


def test_absorbed_attention_agrees_with_expanded(config, model):
    """(b) the two forms of latent attention on the same rows: the
    last position's output of the expanded form against the absorbed
    form over the rows before it (as the window) and its own (as the
    staged column)."""
    params, _ = model
    arch, blk, heads = params["arch"], params["blocks"][1], \
        config["n_head"]
    x = jnp.asarray(numpy.random.RandomState(4).randn(
        3, 17, config["hidden_size"]), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(17), (3, 17))
    q, rows = blocks.Latent.project(arch, blk, x, heads, positions)
    want = blocks.Latent.attend_prompt(arch, blk, q, rows)[:, -1:]
    columns = jnp.swapaxes(rows["kv"], -2, -1)
    got = blocks.Latent.attend_cached(
        arch, blk, tuple(part[:, -1:] for part in q),
        {"kv": columns[..., :16]}, {"kv": columns[..., 16:]},
        jnp.ones((3, 1, 1, 16), bool), jnp.ones((3, 1, 1, 1), bool))
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(want),
                                  rtol=1e-4, atol=1e-5)


def _expert_block(model, uneven=False):
    params, _ = model
    blk = dict(params["blocks"][1])
    if uneven:
        # expert 3 never chosen, expert 5 chosen by every token
        blk["router_bias"] = blk["router_bias"].at[3].set(-10.0) \
            .at[5].set(10.0)
    return params["arch"], blk


def _loop_layer(arch, blk, h):
    """Every expert over every token, one at a time."""
    chosen, weights = moe.route(h, blk["router"], blk["router_bias"],
                                arch.top_k, arch.route_scale)
    y = moe.swiglu(h, blk["shared"])
    for e in range(blk["router"].shape[1]):
        share = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        y = y + share[:, None] * moe.swiglu(
            h, jax.tree.map(lambda w: w[e], blk["experts"]))
    return y, chosen


def test_grouped_experts_agree_with_the_loop_under_uneven_routing(
        config, model):
    """(c) one expert with no token and one with every token: the
    grouped products give what the loop over experts gives, and every
    assignment is computed (no capacity, no drop)."""
    arch, blk = _expert_block(model, uneven=True)
    h = jnp.asarray(numpy.random.RandomState(6).randn(
        41, config["hidden_size"]), jnp.float32)
    got, load = moe.expert_layer(h, blk, arch.top_k, arch.route_scale)
    want, chosen = _loop_layer(arch, blk, h)
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(want),
                                  rtol=1e-4, atol=1e-5)
    load = numpy.asarray(load)
    assert load[3] == 0 and load[5] == 41
    assert load.sum() == 41 * arch.top_k
    numpy.testing.assert_array_equal(
        load, numpy.bincount(numpy.asarray(chosen).ravel(),
                             minlength=len(load)))


def test_the_shares_of_the_experts_add_up_to_the_layer(config, model):
    """(d) the layer run with ``held`` = each quarter of the experts,
    and the shared expert counted once, sums to the whole layer."""
    arch, blk = _expert_block(model)
    h = jnp.asarray(numpy.random.RandomState(7).randn(
        29, config["hidden_size"]), jnp.float32)
    whole, load = moe.expert_layer(h, blk, arch.top_k, arch.route_scale)
    chosen, weights = moe.route(h, blk["router"], blk["router_bias"],
                                arch.top_k, arch.route_scale)
    quarter = config["n_routed_experts"] // 4
    total, loads = moe.swiglu(h, blk["shared"]), []
    for first in range(0, config["n_routed_experts"], quarter):
        held = jax.tree.map(lambda w: w[first:first + quarter],
                            blk["experts"])
        part, part_load = moe.routed_experts(
            h, chosen, weights, held, held=(first, quarter))
        total = total + part
        loads.append(numpy.asarray(part_load))
    numpy.testing.assert_allclose(numpy.asarray(total),
                                  numpy.asarray(whole),
                                  rtol=1e-4, atol=1e-5)
    numpy.testing.assert_array_equal(numpy.concatenate(loads),
                                     numpy.asarray(load))


def test_a_token_that_is_not_live_reads_no_expert(config, model):
    arch, blk = _expert_block(model)
    h = jnp.asarray(numpy.random.RandomState(8).randn(
        6, config["hidden_size"]), jnp.float32)
    live = jnp.asarray([True, False, True, True, False, False])
    got, load = moe.expert_layer(h, blk, arch.top_k, arch.route_scale,
                                 live=live)
    want, _ = moe.expert_layer(h[live], blk, arch.top_k, arch.route_scale)
    assert int(load.sum()) == 3 * arch.top_k
    numpy.testing.assert_allclose(numpy.asarray(got[live]),
                                  numpy.asarray(want), rtol=1e-4, atol=1e-5)
    # the others get the shared expert alone
    numpy.testing.assert_allclose(
        numpy.asarray(got[~live]),
        numpy.asarray(moe.swiglu(h[~live], blk["shared"])),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("t", [0, 1, 77, 2047])
def test_rope_at_a_position_is_the_closed_form(t):
    """(e) pair ``i`` of the row at position ``t`` is turned by
    ``t * theta ** (-2i / R)``."""
    theta, r = 32e6, 8
    x = numpy.random.RandomState(t).randn(2, 1, 3, r).astype("float32")
    got = numpy.asarray(blocks.rope(
        jnp.asarray(x), jnp.full((2, 1), t), theta))
    for i in range(r // 2):
        angle = t * theta ** (-2.0 * i / r)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        numpy.testing.assert_allclose(
            got[..., 2 * i], a * numpy.cos(angle) - b * numpy.sin(angle),
            rtol=1e-4, atol=1e-5)
        numpy.testing.assert_allclose(
            got[..., 2 * i + 1],
            a * numpy.sin(angle) + b * numpy.cos(angle),
            rtol=1e-4, atol=1e-5)


def test_a_tree_without_an_architecture_is_gpt2s_block():
    """(f) the first instance: a dict with ``blocks``/``wqkv`` as
    before goes through the seam to the numbers GPT-2's helpers give
    (the existing decode tests hold the slab's paths to them)."""
    from veles_tpu.ops.attention import attention

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, 2, 16, 4, 11)
    assert blocks.arch_of(params) is blocks.GPT2
    assert not blocks.expert_blocks(params)
    x = jnp.asarray(rng.randn(2, 7, 16).astype("float32"))
    blk = params["blocks"][0]
    got, rows = blocks.block_forward(
        blocks.GPT2, blk, x, 4, jnp.broadcast_to(jnp.arange(7), (2, 7)))
    q, k, v = blocks._block_qkv(blk, x, 4)
    want = x + attention(q, k, v, causal=True).reshape(2, 7, 16) \
        @ blk["wout"] + blk["bout"]
    want = blocks._mlp(blk, want)
    assert set(rows) == {"k", "v"}
    numpy.testing.assert_array_equal(numpy.asarray(got),
                                     numpy.asarray(want))


@pytest.mark.parametrize("tier, named", [
    (dict(paged=True), "paged=True"),
    (dict(quantize="int8"), "quantize='int8'"),
    (dict(quantize="int8-kv"), "quantize='int8-kv'"),
    (dict(mesh=object()), "mesh="),
    (dict(aot=object()), "aot="),
    (dict(prefix_cache=object()), "prefix_cache="),
])
def test_a_tier_built_on_gpt2s_leaves_refuses_the_new_kinds(
        config, model, tier, named):
    """(g) each by name, before anything is placed on the device."""
    from veles_tpu.serving import ContinuousDecoder

    params, table = model
    with pytest.raises(ValueError) as refused:
        ContinuousDecoder(params, table, config["n_head"], slots=2,
                          max_len=32, **tier)
    assert named in str(refused.value)
    assert "attention='mla'" in str(refused.value)


def test_generate_and_the_train_step_refuse_the_new_kinds(config, model):
    params, table = model
    with pytest.raises(ValueError, match="generate"):
        decode.generate(params, table, jnp.zeros((1, 4), jnp.int32),
                        config["n_head"], 2)
    with pytest.raises(ValueError, match="train step"):
        build_transformer_train_step(config["n_head"])(
            params, jnp.zeros((1, 4, config["hidden_size"])),
            jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="page pool"):
        decode.init_slot_state(3, 2, 32, 4, 16, 211, paged=True,
                               arch=params["arch"])


def _scaled_bias(params, by):
    return dict(params, blocks=[
        dict(blk, router_bias=blk["router_bias"] * by)
        if "router" in blk else blk for blk in params["blocks"]])


@pytest.mark.parametrize("fault", ["bias_left_out", "no_rope_on_k",
                                   "dropped_token"])
def test_a_planted_fault_reads_not_correct(reference, config, model,
                                           monkeypatch, fault):
    """(h) what the harness compares (``served_gaps`` against the
    configuration's limit) on tokens served by a program with one
    piece of the mathematics left out."""
    params, table = model
    served = params
    if fault == "bias_left_out":
        # a bias that steers the choice: the configuration's N(0, 0.02)
        # moves a choice as often as bfloat16 rounding does, and no
        # comparison that lets rounding pass can see it go
        params = _scaled_bias(params, 25.0)
        served = _scaled_bias(params, 0.0)
    elif fault == "no_rope_on_k":
        turn = blocks.rope
        monkeypatch.setattr(
            blocks, "rope", lambda x, positions, theta:
            turn(x, positions, theta) if x.ndim == 4 else x)
    else:
        whole = moe.routed_experts

        def dropping(h, chosen, weights, experts, held=None, live=None,
                     routed=None):
            # every other token is over some expert's capacity
            keep = jnp.arange(h.shape[0]) % 2 == 0
            return whole(h, chosen, weights, experts, held,
                         keep if live is None else live & keep, routed)

        monkeypatch.setattr(moe, "routed_experts", dropping)
    rng = numpy.random.RandomState(9)
    prompts = [rng.randint(0, config["vocab_size"], n).tolist()
               for n in (40, 64, 90)]
    # the patched sublayers must be traced: JAX keeps a function's
    # trace whatever jit object asks for it
    jax.clear_caches()
    try:
        _, tokens = _serve(served, table, config["n_head"], prompts, 12)
    finally:
        jax.clear_caches()
    widest = max(float(reference.served_gaps(
        config, params, table, prompt, tokens[row].tolist()).max())
        for row, prompt in enumerate(prompts))
    assert widest > config["limits"]["served_logit_gap"], widest


def test_route_flips_counts_changed_expert_sets(reference, config, model):
    """The calibration's counter: the same precision changes no set;
    float8 operands change some, of (expert layers x tokens)."""
    params, table = model
    tokens = numpy.random.RandomState(10).randint(
        0, config["vocab_size"], 30).tolist()
    same, of = reference.route_flips(config, params, table, tokens[:20],
                                     tokens[20:], operands="float32")
    assert (same, of) == (0, 2 * 29)
    changed, _ = reference.route_flips(config, params, table, tokens[:20],
                                       tokens[20:],
                                       operands="float8_e4m3fn")
    assert 0 < changed <= of
