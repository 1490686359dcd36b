"""Window layers with a ring each beside a global layer, parallel blocks
and four averaged shared experts (``parallel/blocks.py`` kinds
``"swa"`` and ``"nope"``), through the slot engine and
``ContinuousDecoder``, against the plain reference.

Everything here is float32 on the CPU at the benchmark configuration's
rehearsal sizes (``benchmark/configs/command-a-plus-05-2026.json``: a
window of 16, 4 of 16 experts held), on seeded weights from the
reference's own ``init_params`` widened to float32, so that what a
comparison sees is the order of the arithmetic and no rounding of
operands. The reference (``benchmark/references/
command-a-plus-05-2026.py``) has no cache and no ring: the window is a
mask on the score of the whole sequence.
"""

import dataclasses
import functools
import hashlib
import importlib.util
import json
import os

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import attention, slab_write
from veles_tpu.parallel import blocks, decode

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: float32 logits of O(1) computed in another order on each side
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
    return _load("benchmark/references/command-a-plus-05-2026.py",
                 "command_a_reference")


@pytest.fixture(scope="module")
def config():
    return _config("command-a-plus-05-2026")


@pytest.fixture(scope="module")
def model(reference, config):
    params, table = reference.init_params(5, config)
    return jax.tree.map(lambda a: a.astype(jnp.float32), (params, table))


def _prompts(config, lengths, seed=1):
    rng = numpy.random.RandomState(seed)
    return [rng.randint(0, config["vocab_size"], n).tolist()
            for n in lengths]


def _served(params, table, heads, prompts, chunk, chunks):
    """The prompts admitted as one right-padded group, then ``chunks``
    chunks of ``chunk`` steps: ``(logits before each chunk (S, chunks,
    V), tokens (S, chunk * chunks))``."""
    arch = blocks.arch_of(params)
    state = decode.init_slot_state(
        len(params["blocks"]), SLOTS, MAX_LEN, heads,
        table.shape[1] // heads, table.shape[0], dtype=table.dtype,
        arch=arch)
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


def _apart(reference, config, params, table, prompts, chunk=8, chunks=3):
    """The widest distance, over the lanes and the chunks' first steps,
    between the served logits and the reference's full forward."""
    logits, tokens = _served(params, table, config["n_head"], prompts,
                             chunk, chunks)
    widest = 0.0
    for lane, prompt in enumerate(prompts):
        want = numpy.asarray(reference.logits_after(
            dict(config, serving=dict(config["serving"],
                                      n_tokens=chunk * chunks)),
            params, table, prompt, tokens[lane].tolist()))
        widest = max(widest, float(numpy.abs(
            want[::chunk] - logits[lane]).max()))
    return widest, logits, tokens


# -- the ring through prefill and decode ---------------------------------------

@pytest.mark.parametrize("lengths, chunk", [
    # a row longer than the window (its ring takes the last 16), one
    # that crosses the window inside the first chunk, short ones
    ((1, 5, 20, 30), 8),
    # chunks of 3 that straddle the ring's wrap at every offset
    ((3, 17, 32, 12), 3),
], ids=["chunk8", "chunk3"])
def test_prefill_then_decode_through_the_ring_is_the_full_forward(
        reference, config, model, lengths, chunk):
    """Sequences of up to 40 positions against a window of 16: an
    admission longer than the window, chunks that straddle the
    window's edge and the ring's wrap. At each chunk's first step the
    slot's logits are the reference's over the whole sequence so far."""
    params, table = model
    prompts = _prompts(config, lengths)
    widest, logits, _ = _apart(reference, config, params, table, prompts,
                               chunk, 24 // chunk)
    assert widest <= CLOSE["atol"] + CLOSE["rtol"] * numpy.abs(
        logits).max(), widest


def test_the_slot_state_holds_rings_beside_the_global_rows(config, model):
    params, table = model
    heads = config["n_head"]
    state = decode.init_slot_state(
        4, SLOTS, MAX_LEN, heads, table.shape[1] // heads, table.shape[0],
        dtype=table.dtype, arch=blocks.arch_of(params))
    row = config["num_key_value_heads"] * config["head_dim"]
    window = config["sliding_window"]
    assert decode._kv_names(state) == ["k_all", "k_ring", "v_all",
                                       "v_ring"]
    assert [leaf.shape for leaf in state["k_ring"]] \
        == [(SLOTS, row, window)] * 3
    assert [leaf.shape for leaf in state["k_all"]] \
        == [(SLOTS, row, MAX_LEN)]
    holds = decode.slot_holds(params, state)
    assert holds["block_kinds"] == {"swa": 3, "nope": 1}
    assert holds["slot_row_bytes_per_position"] == 2 * row * 4
    assert holds["slot_ring_bytes"] == 3 * 2 * row * window * 4


# -- the held shares add up to the layer ----------------------------------------

def test_the_held_shares_sum_to_the_uncut_layer(reference, config, model):
    """Four chips' shares ``(0, 4) .. (12, 4)`` of a parallel block,
    the attention and the shared experts counted once: the uncut
    reference's block, all 16 experts held (guide section 4)."""
    params, table = model
    routed = config["routed_experts"]
    whole = reference.init_params(5, dict(config, num_experts=routed))[0]
    whole = jax.tree.map(lambda a: a.astype(jnp.float32), whole)
    blk = whole["blocks"][0]
    arch = blocks.arch_of(whole)
    rng = numpy.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 24, config["hidden_size"]), jnp.float32)
    positions = jnp.arange(24)[None]
    kind = blocks.block_kinds(arch, 4)[0]

    def block(held, experts):
        share = dataclasses.replace(arch, held=held)
        return blocks.block_forward(share, dict(blk, experts=experts), x,
                                    config["n_head"], positions,
                                    kind=kind)[0] - x

    count = config["num_experts"]
    parts = [block((first, count), jax.tree.map(
        lambda w, at=first: w[at:at + count], blk["experts"]))
        for first in range(0, routed, count)]
    alone = block((0, count), jax.tree.map(lambda w: w[:0],
                                           blk["experts"]))
    want = block((0, routed), blk["experts"])
    # each share carries the attention and the shared experts once
    got = sum(parts) - (len(parts) - 1) * alone
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(want),
                                  **CLOSE)
    # and the reference's block, all experts held, is the same layer
    stacked = [dict(b, first=jnp.int32(0)) for b in whole["blocks"]]
    ref_logits = reference._logits_at(
        stacked, whole["norm_w"], table, jnp.arange(24) % 211,
        jnp.arange(24), reference.sizes(dict(config, num_experts=routed)),
        "float32")
    served = blocks.head(arch, whole, functools.reduce(
        lambda h, b: blocks.block_forward(
            arch, b[1], h, config["n_head"], positions,
            kind=blocks.block_kinds(arch, 4)[b[0]])[0],
        enumerate(whole["blocks"]), table[jnp.arange(24) % 211][None]),
        table)[0]
    numpy.testing.assert_allclose(numpy.asarray(served),
                                  numpy.asarray(ref_logits), **CLOSE)


# -- planted faults -------------------------------------------------------------

def _window_off_by_one(params, monkeypatch):
    arch = blocks.arch_of(params)
    return dict(params, arch=dataclasses.replace(arch,
                                                 window=arch.window + 1))


def _rope_on_the_global_layer(params, monkeypatch):
    monkeypatch.setattr(blocks.Global, "rotate", True)
    return params


def _shared_summed(params, monkeypatch):
    return dict(params, arch=dataclasses.replace(blocks.arch_of(params),
                                                 shared_scale=1.0))


def _sequential_block(params, monkeypatch):
    return dict(params, arch=dataclasses.replace(
        blocks.arch_of(params), parallel=False),
        blocks=[dict(blk, ffn_norm=blk["attn_norm"])
                for blk in params["blocks"]])


def _replaced_entry_readable(params, monkeypatch):
    def visible(before, length, read, j):
        age = (jnp.arange(read)[None, :] - before[:, None]) % length
        return age >= jnp.maximum(0, length - before)[:, None]

    monkeypatch.setattr(decode, "ring_visible", visible)
    return params


def _norm_without_the_mean(params, monkeypatch):
    return dict(params, arch=dataclasses.replace(blocks.arch_of(params),
                                                 norm="rms"))


@pytest.mark.parametrize("plant", [
    _window_off_by_one, _rope_on_the_global_layer, _shared_summed,
    _sequential_block, _replaced_entry_readable, _norm_without_the_mean,
], ids=lambda plant: plant.__name__.strip("_"))
def test_a_planted_fault_fails_the_comparison_the_program_passes(
        reference, config, model, monkeypatch, plant):
    params, table = model
    prompts = _prompts(config, (1, 5, 20, 30))
    jax.clear_caches()
    try:
        faulty = plant(params, monkeypatch)
        widest, _, _ = _apart(reference, config, faulty, table, prompts)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert widest > FAULT, widest


# -- the other models trace as they did ---------------------------------------

#: sha256 of the jaxpr text of each program at the configuration's
#: rehearsal sizes, as the tree before the window and parallel blocks
#: traced them (an admission of 2 rows of 16, a chunk of 4 steps over
#: 32 positions of a 4 x 64 slab), but for the routed experts' two
#: gathers, which take their rows by clipped index
#: (``ops/moe.routed_experts``)
PROGRAMS = {
    "gpt2-medium.admit":
        "9eebe7b38cf3f7b4d0a0a6260c6ba5bc6b7f6e7ec72a99eb330723bb38c9d76e",
    "gpt2-medium.chunk":
        "1e1d0e3789428b48fdac701bcfd10d1e70d52922d5981dfcda7cf135e953e5fd",
    "lfm2-8b-a1b.admit":
        "9e09900f74384139c770c4d6640ae907009d2e45c25331774a0a0bfdaf660c5b",
    "lfm2-8b-a1b.chunk":
        "0c28567c1c843837eb2733c959220bea013cae1f0e1ced5adc00f272bfefdc4b",
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_a_model_without_the_new_properties_traces_as_before(program):
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


# -- the pieces: the prompt's attend, the ring's write ------------------------

@pytest.mark.parametrize("window", [0, 100], ids=["causal", "window100"])
def test_the_splash_kernel_gives_xla_s_prompt_attention(monkeypatch,
                                                        window):
    """Grouped heads (4 over 2 of 128) at 256 positions, causal and
    within a window: the kernel (interpreted) where the rule would take
    it on the chip, against XLA's form."""
    rng = numpy.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 256, 4, 128), jnp.float32)
    k, v = (jnp.asarray(rng.randn(2, 256, 2, 128), jnp.float32)
            for _ in range(2))
    assert attention.prompt_path(2, 256, 4, 128) == "xla"
    want = attention.grouped_attention(q, k, v, window)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "SCORE_BYTES", 0)
    assert attention.prompt_path(2, 256, 4, 128) == "kernel"
    assert attention.prompt_path(2, 256, 4, 64) == "xla"
    got = attention.grouped_attention(q, k, v, window)
    numpy.testing.assert_allclose(numpy.asarray(got), numpy.asarray(want),
                                  rtol=1e-4, atol=1e-4)


def test_the_write_kernel_and_the_loop_write_a_ring_alike():
    """A ring of 256 positions: blocks of 8 that wrap past its end, one
    that ends on it, and plain ones; the kernel (interpreted) and the
    loop leave the same bits, each block at ``before mod 256``."""
    rng = numpy.random.RandomState(0)
    before = jnp.asarray([250, 255, 248, 3, 512 + 130, 127], jnp.int32)
    leaves = [jnp.asarray(rng.randn(6, 16, 256), jnp.float32)
              for _ in range(2)]
    staged = [jnp.asarray(rng.randn(6, 16, 8), jnp.float32)
              for _ in range(2)]
    rings = [True, True]
    got = slab_write.write_blocks(leaves, staged, before, rings=rings)
    loop = slab_write.write_blocks_loop(leaves, staged, before, rings=rings)
    want = [numpy.array(leaf) for leaf in leaves]
    for leaf, block in zip(want, staged):
        for s, at in enumerate(numpy.asarray(before)):
            leaf[s, :, (at + numpy.arange(8)) % 256] = \
                numpy.asarray(block[s]).T
    for one in (got, loop):
        for leaf, expect in zip(one, want):
            assert numpy.array_equal(numpy.asarray(leaf), expect)


def _ring_chunks(params, table, heads, lengths, chunks=2):
    """Prompts of ``lengths`` (0: an idle lane) admitted into a slab of
    512 positions whose window blocks keep a ring of 256, then
    ``chunks`` chunks of 8 steps: ``(attend path, tokens, the logits
    before each chunk and after the last)``."""
    state = decode.init_slot_state(
        4, len(lengths), 512, heads, table.shape[1] // heads,
        table.shape[0], dtype=table.dtype, arch=blocks.arch_of(params))
    padded = numpy.zeros((len(lengths), 512), numpy.int32)
    for j, n in enumerate(lengths):
        padded[j, :n] = numpy.random.RandomState(j).randint(
            0, table.shape[0], n)
    live = jnp.asarray([n > 0 for n in lengths])
    state = decode.slot_admit_many(
        params, table, heads, state,
        jnp.arange(len(lengths), dtype=jnp.int32), table[padded],
        jax.random.split(jax.random.key(0), len(lengths)),
        jnp.asarray([max(n, 1) for n in lengths], jnp.int32))
    path = decode.slot_attend_path(params, state)
    logits, tokens = [], []
    for _ in range(chunks):
        logits.append(numpy.asarray(state["logits"]))
        state, emitted = decode.slot_step_many(params, table, heads, state,
                                               live, 8, span=512)
        tokens.append(numpy.asarray(decode.split_emitted(emitted)[0]))
    logits.append(numpy.asarray(state["logits"]))
    return path, numpy.concatenate(tokens), numpy.stack(logits)


def test_the_slab_kernel_attends_rings_and_rows_as_xla_does(
        reference, config, monkeypatch):
    """Heads of 128 (whole lane tiles, the kernel's rule), a window of
    256: chunks that cross the ring's wrap (250, 300 past the window,
    so wrapped since admission), a short prompt and an idle lane. The
    kernel (interpreted, each K/V head's rows once for its group of
    query heads, a ring's entries by their age) leaves the logits and
    tokens ``_cache_attend`` leaves."""
    from veles_tpu.ops import slab_attention

    wide = dict(config, hidden_size=256, num_attention_heads=4, n_head=4,
                num_key_value_heads=2, head_dim=128, sliding_window=256)
    params, table = jax.tree.map(lambda a: a.astype(jnp.float32),
                                 reference.init_params(6, wide))
    lengths = (250, 300, 5, 0)
    jax.clear_caches()
    try:
        path, want, logits = _ring_chunks(params, table, 4, lengths)
        monkeypatch.setattr(slab_attention, "on_tpu", lambda: True)
        monkeypatch.setattr(slab_attention, "device_kind",
                            lambda: "TPU v5 lite")
        jax.clear_caches()
        kernel_path, got, kernel_logits = _ring_chunks(params, table, 4,
                                                       lengths)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert (path, kernel_path) == ("xla", "kernel")
    numpy.testing.assert_array_equal(got[:, :3], want[:, :3])
    numpy.testing.assert_allclose(kernel_logits[:, :3], logits[:, :3],
                                  **CLOSE)


# -- through ContinuousDecoder ------------------------------------------------

def _decoder(model, config, slots=2, **kwargs):
    from veles_tpu.serving import ContinuousDecoder

    params, table = model
    return ContinuousDecoder(params, table, config["n_head"], slots=slots,
                             max_len=MAX_LEN, n_tokens=6, **kwargs)


def test_the_decoder_serves_prompts_past_the_window_and_books_them(
        reference, config, model):
    """Five requests through two slots, two of them longer than the
    window: each answer is the reference's own greedy continuation,
    and the books say what a slot holds and how the prompts went."""
    params, table = model
    decoder = _decoder(model, config)
    prompts = _prompts(config, (3, 17, 1, 40, 30), seed=2)
    rids = [decoder.submit(numpy.asarray(p), 6) for p in prompts]
    decoder.drain_pipelined(4)
    for rid, prompt in zip(rids, prompts):
        gaps = reference.served_gaps(config, params, table, prompt,
                                     decoder.results[rid])
        assert len(decoder.results[rid]) == 6 and gaps.max() <= 2e-4
    assert decoder.slot_holds["block_kinds"] == {"swa": 3, "nope": 1}
    assert decoder.slot_holds["slot_ring_bytes"] > 0
    assert decoder.admits_past_window == 3
    assert decoder.prompt_paths["xla"] >= 1 \
        and decoder.prompt_paths["kernel"] == 0
    said = decoder._book_attend_path(4)
    assert said["window_path"] == said["attend_path"] == "xla"


@pytest.mark.parametrize("admit_tokens, admissions", [
    (0, 1), (64, 2), (32, 4)], ids=["whole", "two_rows", "one_row"])
def test_an_admission_takes_at_most_admit_tokens_positions(
        config, model, admit_tokens, admissions):
    """Four prompts of the 32 bucket: one admission of all four where
    the model sets no limit, else as many as ``Arch.admit_tokens``
    positions allow."""
    params, table = model
    arch = dataclasses.replace(blocks.arch_of(params),
                               admit_tokens=admit_tokens)
    decoder = _decoder((dict(params, arch=arch), table), config, slots=4)
    for prompt in _prompts(config, (20, 21, 25, 30), seed=4):
        decoder.submit(numpy.asarray(prompt), 2)
    decoder.drain_pipelined(2)
    assert decoder.dispatch_counts["admit"] == admissions
    assert decoder.dispatch_counts["admit_requests"] == 4


@pytest.mark.parametrize("kwargs, tier", [
    (dict(paged=True), r"paged=True \(the page pool\)"),
    (dict(quantize="int8"), "quantize='int8'"),
    (dict(mesh="a mesh"), r"mesh= \(tensor-parallel serving\)"),
    (dict(paged=True, prefix_cache="a cache"), r"paged=True"),
], ids=["paged", "int8", "mesh", "prefix"])
def test_the_tiers_built_on_gpt2_s_leaves_refuse_the_kinds_by_name(
        config, model, kwargs, tier):
    import re

    with pytest.raises(ValueError) as refused:
        _decoder(model, config, **kwargs)
    text = str(refused.value)
    assert re.search(tier, text), text
    assert "3 x 'swa'" in text and "for kind 'nope'" in text, text
