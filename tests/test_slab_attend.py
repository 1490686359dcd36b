"""The dense slab's ragged-length attend kernel and the rule that takes it.

``ops/slab_attention.slab_attend`` (a Pallas TPU kernel: each slot's
K and V once, in tiles of positions, up to the slot's own length) in
interpret mode against ``parallel/blocks._cache_attend`` (every slot
over the rectangular window), with and without the chunk's staged
columns; the slot engine's tokens, kernel path against XLA path; the
rule (``use_slab_kernel``) by what it reads; the decoder's books. The
tests steer the platform (``slab_attention.on_tpu`` and
``device_kind``), never the path: what they run is what the TPU's rule
picks, interpreted.

What "agrees" means: for float32 leaves, float32 round-off. For
bfloat16 leaves both formulations round the softmax weights to
bfloat16 before ``p . V`` (the kernel its un-normalised ``exp(s - m)``,
``_cache_attend`` the normalised ``p``), so each lies within that
rounding of the float32 computation over the same values and the two
within it of each other; nothing else is rounded (the planted faults
below move the answer by a hundred times more).
"""

import json
import urllib.request

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import slab_attention
from veles_tpu.parallel import blocks, decode
from veles_tpu.parallel.transformer_step import init_transformer_params

TILE = slab_attention.TILE
SLOTS, HEADS, HEAD_DIM, MAX_LEN, SPAN, CHUNK = 8, 2, 64, 3 * TILE, \
    2 * TILE + 128, 4
#: 0 (an idle lane reads nothing), 1, a tile's edge - 1, the edge, the
#: edge + 1, the second tile's last position, the span itself, mid-tile
LENGTHS = (0, 1, TILE - 1, TILE, TILE + 1, 2 * TILE - 1, SPAN, 300)
LIMIT = {"float32": 2e-5, "bfloat16": 1e-2}


def _operands(dtype, seed=0):
    rng = numpy.random.RandomState(seed)

    def leaf(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    return {"q": leaf(SLOTS, 1, HEADS, HEAD_DIM),
            "k": leaf(SLOTS, HEADS * HEAD_DIM, MAX_LEN),
            "v": leaf(SLOTS, HEADS * HEAD_DIM, MAX_LEN),
            "k_tail": leaf(SLOTS, HEADS, HEAD_DIM, CHUNK),
            "v_tail": leaf(SLOTS, HEADS, HEAD_DIM, CHUNK),
            "lengths": jnp.asarray(LENGTHS, jnp.int32)}


def _window(op, dtype=None):
    """The operands as ``_cache_attend`` takes them: the window of
    ``SPAN`` positions, heads apart, and its mask."""
    def apart(leaf):
        leaf = leaf[..., :SPAN].reshape(SLOTS, HEADS, HEAD_DIM, SPAN)
        return leaf if dtype is None else leaf.astype(dtype)

    mask = jnp.arange(SPAN)[None, :] < op["lengths"][:, None]
    return apart(op["k"]), apart(op["v"]), mask[:, None, None, :]


def _staged(op, j, dtype=None):
    visible = jnp.broadcast_to(jnp.arange(CHUNK)[None, :] <= j,
                               (SLOTS, CHUNK))[:, None, None, :]
    k, v = op["k_tail"], op["v_tail"]
    if dtype is not None:
        k, v = k.astype(dtype), v.astype(dtype)
    return k, v, visible


def _both(op, j):
    """``(kernel path, _cache_attend, the float32 computation over the
    same values)`` at step ``j`` of a chunk; ``j`` None: no staged
    columns, the kernel's parts normalised as they are."""
    k, v, mask = _window(op)
    wide = jnp.float32
    if j is None:
        acc, m, l = slab_attention.slab_attend(
            op["q"], op["k"], op["v"], op["lengths"], SPAN)
        got = (acc / l[..., None])[:, None]
        want = blocks._cache_attend(op["q"], k, v, mask)
        exact = blocks._cache_attend(op["q"].astype(wide),
                                     *_window(op, wide))
    else:
        got = slab_attention.join_tail(
            op["q"], slab_attention.slab_attend(
                op["q"], op["k"], op["v"], op["lengths"], SPAN),
            *_staged(op, j))
        want = blocks._cache_attend(op["q"], k, v, mask,
                                    tail=_staged(op, j))
        exact = blocks._cache_attend(
            op["q"].astype(wide), *_window(op, wide),
            tail=_staged(op, j, wide))
    return got, want, exact


def _apart(one, other, live=slice(None)):
    return float(jnp.max(jnp.abs(one[live] - other[live])))


@pytest.mark.parametrize("j", [None, 0, 1, 2, 3],
                         ids=lambda j: "no_tail" if j is None
                         else "step%d" % j)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_agrees_with_cache_attend(dtype, j):
    """Ragged lengths that include 0, 1, a tile's edge +- 1 and the
    span itself, at every step of a chunk and without the staged
    columns (there a slot of length 0 has no answer: the kernel says
    ``(0, -1e30, 0)`` and the lane is left out)."""
    op = _operands(dtype)
    got, want, exact = _both(op, j)
    live = slice(1, None) if j is None else slice(None)
    assert got.shape == want.shape == (SLOTS, 1, HEADS, HEAD_DIM)
    assert got.dtype == jnp.float32
    assert _apart(got, want, live) < LIMIT[dtype]
    assert _apart(got, exact, live) < LIMIT[dtype]
    # never narrower: no further from the float32 computation than
    # the formulation it replaces, to that formulation's own error
    assert _apart(got, exact, live) < 2 * _apart(want, exact, live) \
        + LIMIT["float32"]


def test_a_slot_of_length_zero_reads_nothing_and_says_so():
    op = _operands("float32")
    acc, m, l = slab_attention.slab_attend(
        op["q"], op["k"], op["v"], op["lengths"], SPAN)
    assert not acc[0].any() and not l[0].any()
    assert (m[0] == -1e30).all()
    # and the walk has no visit for it
    slot, _, total = slab_attention.visit_table(op["lengths"], SPAN)
    assert 0 not in numpy.asarray(slot)[:int(total[0])]


def test_the_walk_visits_each_slots_live_tiles_and_no_other():
    """The table of visits is the kernel's whole schedule: one DMA a
    leaf and one pass a visit, ``total`` visits. A tile past a slot's
    length is not in it; a length past the span counts to the span."""
    lengths = jnp.asarray(LENGTHS + (MAX_LEN + 5,), jnp.int32)
    slot, at, total = (numpy.asarray(a) for a in
                       slab_attention.visit_table(lengths, SPAN))
    live = [-(-min(n, SPAN) // TILE) for n in numpy.asarray(lengths)]
    assert int(total[0]) == sum(live)
    want = [(s, t) for s, n in enumerate(live) for t in range(n)]
    assert list(zip(slot[:sum(live)], at[:sum(live)])) == want
    assert slot.shape == at.shape == (len(live) * -(-SPAN // TILE),)


@pytest.mark.parametrize("fault", ["tail_left_out", "one_position_past",
                                   "last_tile_left_out"])
def test_a_planted_fault_breaks_the_agreement(fault, monkeypatch):
    """The agreement above catches: the staged columns left out of the
    softmax, a position past the slot's length read, a slot's last
    live tile not read. Each moves the answer far past the limit."""
    if fault == "tail_left_out":
        monkeypatch.setattr(
            slab_attention, "join_tail",
            lambda q, parts, *tail:
                (parts[0] / parts[2][..., None])[:, None])
    elif fault == "one_position_past":
        attend = slab_attention.slab_attend
        monkeypatch.setattr(
            slab_attention, "slab_attend",
            lambda q, k, v, lengths, span:
                attend(q, k, v, lengths + 1, span))
    else:
        table = slab_attention.visit_table
        monkeypatch.setattr(
            slab_attention, "visit_table",
            lambda lengths, span: table(
                jnp.maximum(lengths - TILE, 1), span))
    for dtype in ("float32", "bfloat16"):
        got, want, _ = _both(_operands(dtype), 1)
        assert _apart(got, want, slice(1, None)) > 10 * LIMIT[dtype]


# -- the slot engine's tokens, kernel path against XLA path --------------------

EMBED, BLOCKS, VOCAB = HEADS * HEAD_DIM, 2, 31
PROMPTS = (5, TILE + 40, TILE - 1, 0)       # the last lane stays idle


@pytest.fixture(scope="module")
def toy():
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, BLOCKS, EMBED, HEADS, VOCAB)
    table = jnp.asarray(
        rng.randn(VOCAB, EMBED).astype(numpy.float32) * 0.3)
    return params, table


def _steer(monkeypatch, on_tpu=True, kind="TPU v5 lite"):
    monkeypatch.setattr(slab_attention, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(slab_attention, "device_kind", lambda: kind)


@pytest.fixture
def on_the_chip(monkeypatch):
    """The rule as the TPU reads it; the kernel itself still resolves
    interpret mode from the real platform. One jitted chunk serves
    both paths, so whatever was traced on the other side goes."""
    jax.clear_caches()
    _steer(monkeypatch)
    yield
    jax.clear_caches()


def _chunks(params, table, dtype, chunks=2):
    """Admit ``PROMPTS`` and run ``chunks`` chunks of ``CHUNK`` steps:
    ``(attend path, tokens (steps, live slots), final logits)``."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    table = table.astype(dtype)
    rng = numpy.random.RandomState(3)
    state = decode.init_slot_state(BLOCKS, len(PROMPTS), 2 * TILE, HEADS,
                                   HEAD_DIM, VOCAB, dtype=dtype)
    for slot, n in enumerate(PROMPTS):
        if n:
            prompt = jnp.asarray(rng.randint(0, VOCAB, (1, n)))
            state = decode.slot_admit(params, table, HEADS, state,
                                      jnp.int32(slot), table[prompt])
    path = decode.slot_attend_path(params, state)
    active = jnp.asarray([n > 0 for n in PROMPTS])
    tokens = []
    for _ in range(chunks):
        state, emitted = decode.slot_step_many(
            params, table, HEADS, state, active, CHUNK,
            span=TILE + 128)
        tokens.append(numpy.asarray(emitted)[:, :3])
    return path, numpy.concatenate(tokens), \
        numpy.asarray(state["logits"][:3])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_slot_step_many_tokens_are_the_xla_paths(toy, dtype, request):
    """A toy GPT-2 block: the chunk's tokens through the kernel are
    the tokens through ``_cache_attend``, slot by slot, with an idle
    lane beside them (it reads nothing and its tokens are no one's),
    and the logits it leaves agree to the leaves' rounding. (With
    bfloat16 leaves the two round the softmax weights at different
    scales, so a near tie of two logits could flip a token: these
    prompts have none, as four seeds of five have none.)"""
    jax.clear_caches()
    path, want, logits = _chunks(*toy, dtype)
    assert path == "xla"
    request.getfixturevalue("on_the_chip")
    path, got, kernel_logits = _chunks(*toy, dtype)
    assert path == "kernel"
    numpy.testing.assert_array_equal(got, want)
    numpy.testing.assert_allclose(
        kernel_logits, logits, rtol=0,
        atol=1e-4 if dtype == "float32" else 5e-2)


def _kernel_calls(jaxpr):
    """Call sites of the kernel (it is lowered once, in a jitted
    function of its own, and called from every block)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call" \
            and eqn.params["name"] == "slab_attend"
        n += sum(_kernel_calls(sub) for sub in
                 jax.core.jaxprs_in_params(eqn.params))
    return n


def _traced_chunk(params, table, state, span=TILE + 128):
    """The chunk program :func:`decode.slot_fns` builds for ``state``'s
    own place, traced as the decoder's dispatch would trace it."""
    chunk = decode.slot_fns(state)[2].__wrapped__
    return chunk.trace(params, table, HEADS, state,
                       jnp.ones((state["lengths"].shape[0],), bool),
                       CHUNK, 1.0, False, 0, span).jaxpr


def test_the_chunk_program_holds_the_kernel_once_a_block(toy, on_the_chip):
    """The traced chunk: one ``slab_attend`` call a block inside the
    scan's body, and no value of the window's size, float32 or the
    leaves' type: nothing slices, masks or widens every slot's K or V
    at the span."""
    params, table = jax.tree.map(lambda a: a.astype(jnp.bfloat16), toy)
    state = decode.init_slot_state(BLOCKS, 4, 2 * TILE, HEADS, HEAD_DIM,
                                   VOCAB, dtype=jnp.bfloat16)
    traced = _traced_chunk(params, table, state)
    assert _kernel_calls(traced.jaxpr) == BLOCKS
    text = str(traced)
    for dtype in ("f32", "bf16", "bool"):
        assert "%s[4,%d,%d]" % (dtype, EMBED, TILE + 128) not in text
        assert "%s[4,%d]" % (dtype, TILE + 128) not in text
        assert "%s[4,%d,%d,%d]" % (dtype, HEADS, HEAD_DIM, TILE + 128) \
            not in text


def test_a_state_nobody_placed_keeps_the_window(toy, on_the_chip):
    """A state that an outer trace holds says nothing of where it
    lies (a jit may shard it over a mesh's Auto axes and leave no word
    of it in the tracers' types), so its steps keep ``_cache_attend``
    on the TPU too."""
    params, table = jax.tree.map(lambda a: a.astype(jnp.bfloat16), toy)
    state = decode.init_slot_state(BLOCKS, 4, 2 * TILE, HEADS, HEAD_DIM,
                                   VOCAB, dtype=jnp.bfloat16)
    traced = jax.make_jaxpr(
        lambda st: decode._slot_step_many(
            params, table, HEADS, st, jnp.ones((4,), bool), CHUNK,
            span=TILE + 128))(state)
    assert _kernel_calls(traced.jaxpr) == 0
    assert "bf16[4,%d,%d]" % (EMBED, TILE + 128) in str(traced)


def test_a_serve_mesh_keeps_the_window_and_the_books_say_so(
        toy, on_the_chip):
    """Head-sharded leaves on the TPU: the chunk program that
    ``slot_fns`` builds for the sharded state holds no kernel (the
    tracers in it do not say they are sharded: the place the program
    was built for does), and the decoder's books give the same answer
    from the state it holds."""
    mesh = jax.sharding.Mesh(numpy.array(jax.devices()[:2]), ("model",))
    params, table = jax.tree.map(lambda a: a.astype(jnp.bfloat16), toy)
    state = decode.init_slot_state(BLOCKS, 4, 2 * TILE, HEADS, HEAD_DIM,
                                   VOCAB, dtype=jnp.bfloat16)
    assert decode.slot_attend_path(params, state) == "kernel"
    state = decode.init_slot_state(BLOCKS, 4, 2 * TILE, HEADS, HEAD_DIM,
                                   VOCAB, dtype=jnp.bfloat16, mesh=mesh)
    assert len(state["k"][0].sharding.device_set) == 2
    assert decode.slot_attend_path(params, state) == "xla"
    traced = _traced_chunk(params, table, state)
    assert _kernel_calls(traced.jaxpr) == 0
    assert "bf16[4,%d,%d]" % (EMBED, TILE + 128) in str(traced)
    # and it runs: the mesh's tokens are the one chip's XLA tokens
    active = jnp.ones((4,), bool)
    _, sharded_tokens = decode.slot_step_many(
        params, table, HEADS, state, active, CHUNK, span=TILE + 128)
    single = decode.init_slot_state(BLOCKS, 4, 2 * TILE, HEADS, HEAD_DIM,
                                    VOCAB, dtype=jnp.bfloat16)
    _, tokens = decode.slot_step_many(
        params, table, HEADS, single, active, CHUNK, span=TILE + 128)
    numpy.testing.assert_array_equal(sharded_tokens, tokens)


# -- the rule -------------------------------------------------------------------

def _leaf(shape=(4, 128, 2 * TILE), dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _one_device():
    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


RULE_CASES = {
    # what the rule reads: (leaf, on the TPU, its kind, the answer)
    "platform_cpu": (_leaf(), False, "cpu", False),
    "platform_tpu": (_leaf(), True, "TPU v5 lite", True),
    "vmem_64_mib": (_leaf(), True, "TPU v5p", True),
    "vmem_unknown_kind": (_leaf(), True, "TPU v9", False),
    "vmem_too_small_for_the_buffers":
        (_leaf((4, 8192, 2 * TILE), jnp.float32), True, "TPU v5p", False),
    "dtype_float32": (_leaf(dtype=jnp.float32), True, "TPU v5 lite", True),
    "dtype_int8": (_leaf(dtype=jnp.int8), True, "TPU v5 lite", False),
    "shape_int8_kv_tier": (_leaf((4, 2, 64, 2 * TILE)), True,
                           "TPU v5 lite", False),
    "shape_width_not_lanes": (_leaf((4, 64, 2 * TILE)), True,
                              "TPU v5 lite", False),
    "shape_max_len_not_tiles": (_leaf((4, 128, TILE + 128)), True,
                                "TPU v5 lite", False),
    "shape_max_len_tiles": (_leaf((4, 1024, 4 * TILE)), True,
                            "TPU v5 lite", True),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_rule_reads_platform_vmem_dtype_and_shapes(case, monkeypatch):
    leaf, on_tpu, kind, want = RULE_CASES[case]
    _steer(monkeypatch, on_tpu, kind)
    assert slab_attention.use_slab_kernel(leaf, _one_device()) is want


def test_the_claim_is_a_share_of_the_chips_vmem(monkeypatch):
    """100 of a v5e's 128 MiB, 50 of a v5p's 64; none for a chip whose
    VMEM the module does not know, and none on the CPU."""
    for kind, want in (("TPU v5 lite", 100 << 20), ("TPU v5p", 50 << 20),
                       ("TPU v9", None), ("cpu", None)):
        _steer(monkeypatch, kind=kind)
        assert slab_attention.vmem_claim() == want


def test_the_rule_reads_the_place(monkeypatch):
    """Leaves sharded over a serve mesh keep the XLA path (a bare
    ``pallas_call`` cannot be partitioned), and so do leaves nobody
    can place; the same leaves known to lie on one device take the
    kernel. The place is an argument, not read off the leaf: inside a
    program the leaf is a tracer, which does not say."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    _steer(monkeypatch)
    mesh = jax.sharding.Mesh(numpy.array(jax.devices()[:2]), ("model",))
    leaf = _leaf()
    assert slab_attention.use_slab_kernel(leaf, _one_device()) is True
    assert slab_attention.use_slab_kernel(
        leaf, NamedSharding(mesh, P(None, "model"))) is False
    assert slab_attention.use_slab_kernel(
        leaf, NamedSharding(mesh, P())) is False
    assert slab_attention.use_slab_kernel(leaf, None) is False


@pytest.mark.parametrize("kind", ["mha", "mha_int8_kv", "mla"])
def test_the_rule_reads_the_attention_kind(kind, toy, monkeypatch):
    """``blocks.attend_path``: GPT-2's float slab takes the kernel;
    the int8-KV tier and latent attention keep ``_cache_attend``."""
    _steer(monkeypatch)
    params, _ = toy
    arch = blocks.GPT2
    if kind == "mla":
        arch = blocks.Arch(attention="mla", kv_rank=96, nope_dim=32,
                           rope_dim=32)
        params = dict(params, arch=arch)
    state = jax.eval_shape(lambda: decode.init_slot_state(
        BLOCKS, 4, 2 * TILE, HEADS, HEAD_DIM, VOCAB, dtype=jnp.bfloat16,
        quantized=kind == "mha_int8_kv", arch=arch))
    assert blocks.attend_path(params, state, _one_device()) == \
        ("kernel" if kind == "mha" else "xla")


# -- the decoder's books --------------------------------------------------------

@pytest.fixture
def observability(tmp_path, monkeypatch):
    """A recorder of its own, the tracer and the registry on; what
    other suites also touch is put back."""
    from veles_tpu.core import logger as logger_mod
    from veles_tpu.core.logger import EventRecorder
    from veles_tpu.observe.metrics import get_metrics_registry
    from veles_tpu.observe.tracing import get_tracer

    events_path = str(tmp_path / "events.jsonl")
    recorder = EventRecorder()
    recorder.open(events_path)
    monkeypatch.setattr(logger_mod, "_event_recorder", recorder)
    tracer, registry = get_tracer(), get_metrics_registry()
    was_traced, was_metered = tracer.enabled, registry.enabled
    tracer.enable()
    registry.reset()
    registry.enable()
    yield events_path
    recorder.close()
    tracer.enabled = was_traced
    registry.reset()
    registry.enabled = was_metered


def _serve(toy, tmp_path, events_path):
    """One request through ``GenerateAPI`` over HTTP: ``(health,
    metrics text, dispatch spans, the decoder)``."""
    from veles_tpu.observe.trace_export import export_chrome_trace
    from veles_tpu.serving import GenerateAPI

    params, table = toy
    api = GenerateAPI(params, table, HEADS, slots=2, max_len=2 * TILE,
                      n_tokens=5, chunk=2, port=0)
    # (the waste plane is the process's: what it held before is not
    # this server's)
    before = dict(api.decoder.scope.waste)
    api.start()
    try:
        url = "http://127.0.0.1:%d" % api.port
        request = urllib.request.Request(
            url + "/generate", data=json.dumps(
                {"tokens": list(range(1, 20))}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(request, timeout=300) as answer:
            tokens = json.loads(answer.read())["tokens"]
        with urllib.request.urlopen(url + "/healthz", timeout=10) as got:
            health = json.loads(got.read())
        with urllib.request.urlopen(url + "/metrics", timeout=10) as got:
            metrics = got.read().decode()
        decoder = api.decoder
    finally:
        api.stop()
    out = str(tmp_path / "trace.json")
    export_chrome_trace(events_path, out)
    with open(out) as fin:
        spans = [e for e in json.load(fin)["traceEvents"]
                 if e["name"] == "decode.dispatch"]
    waste = {cause: n - before.get(cause, 0)
             for cause, n in decoder.scope.waste.items()}
    return tokens, health, metrics, spans, decoder, waste


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_the_decoder_books_how_every_dispatch_attends(
        path, toy, observability, tmp_path, request):
    """``/healthz``, the ``decode.dispatch`` span and the labelled
    counter say ``attend_path``; the waste plane books a dispatch the
    kernel served as ``tile_pad`` (the dead lanes of each slot's last
    tile) and not as ``span_overshoot``. On the CPU every dispatch is
    ``xla``; with the platform steered every one is ``kernel``."""
    jax.clear_caches()
    if path == "kernel":
        request.getfixturevalue("on_the_chip")
    tokens, health, metrics, spans, decoder, waste = _serve(
        toy, tmp_path, observability)
    chunks = decoder.dispatch_counts["chunk"]
    other = "xla" if path == "kernel" else "kernel"
    assert chunks >= 2 and len(tokens) == 5
    assert health["counters"]["attend_path"] == {path: chunks, other: 0}
    assert 'veles_decode_attend_dispatches_total{path="%s"} %d' \
        % (path, chunks) in metrics
    assert other not in {e["args"]["attend_path"] for e in spans}
    assert len(spans) == chunks
    booked, spared = ("tile_pad", "span_overshoot") if path == "kernel" \
        else ("span_overshoot", "tile_pad")
    assert waste[booked] > 0 and waste[spared] == 0
    assert decoder.scope.debug_snapshot()["dispatches"][-1][1] == \
        ("kernel" if path == "kernel" else "dense")
    # (the chunk's blocks go to the slab by the loop: the write's rule
    # is not steered here)
    assert health["counters"]["block_write_path"] == {
        "kernel": 0, "loop": chunks}


def test_the_decoder_books_how_every_chunk_writes_its_blocks(
        toy, observability, tmp_path, monkeypatch):
    """``/healthz`` and the ``decode.dispatch`` span say
    ``block_write_path``, once a chunk: with the write's rule steered
    onto the chip every chunk's blocks go by ``ops/slab_write``'s
    kernel (interpreted here), and the answer is the loop's."""
    from veles_tpu.ops import slab_write

    jax.clear_caches()
    want = _serve(toy, tmp_path, observability)[0]
    monkeypatch.setattr(slab_write, "on_tpu", lambda: True)
    monkeypatch.setattr(slab_write, "device_kind", lambda: "TPU v5 lite")
    jax.clear_caches()
    try:
        tokens, health, _, spans, decoder, _ = _serve(
            toy, tmp_path, observability)
    finally:
        jax.clear_caches()
    chunks = decoder.dispatch_counts["chunk"]
    assert chunks >= 2 and tokens == want
    assert health["counters"]["block_write_path"] == {
        "kernel": chunks, "loop": 0}
    # (the recorder holds the first server's spans too: the loop's)
    assert [e["args"]["block_write_path"] for e in spans] \
        == ["loop"] * (len(spans) - chunks) + ["kernel"] * chunks


def test_the_page_pool_has_no_attend_path_books(toy):
    from veles_tpu.serving import ContinuousDecoder

    params, table = toy
    decoder = ContinuousDecoder(params, table, HEADS, slots=2, max_len=32,
                                n_tokens=4, paged=True, page_size=8)
    assert decoder.attend_paths is None
    assert decoder._book_attend_path(4) == {}
