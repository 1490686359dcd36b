"""Scopes and spans the program writes for a profiler capture (ISSUE 27).

A capture names a device op by its instruction's text and holds no
``op_name``, so the ``jax.named_scope``s of the two hot programs reach a
traced op only through ``observe/xla_stats.scope_table``: these tests
hold the table to every scope the programs write, to telling backward
from forward, to compiling nothing while tracing is off or a window is
open, and to being right when the persistent compile cache hands back a
binary from before the scopes existed. The spans the host side gained
(``engine.*``, ``decision.settle``, ``loader.serve_sweep``,
``serve.drive_*``, ``unit.run.<unit>``) are held to the disabled-path
contract: tracing off, no span is allocated.
"""

import importlib
import json
import pkgutil
import re
import urllib.request

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.core import prng
from veles_tpu.core.logger import EventRecorder
from veles_tpu.core.units import Unit, run_span_label
from veles_tpu.dummy import DummyLauncher
from veles_tpu.observe import tracing, xla_stats
from veles_tpu.observe.tracing import NULL_SPAN, get_tracer
from veles_tpu.parallel import fused

#: the pattern the benchmark's trace reduction takes a host event for a
#: span of the program by (benchmark/harness/trace.py SPAN_NAME)
SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
LOWERED = "/jax/core/compile/jaxpr_to_mlir_module_duration"

TRAIN_SCOPES = ("data", "fwd", "update", "l0_dense", "l1_dense")
# "cache.read" is written (tests/test_decode.py holds it to one window
# per leaf) and owns no instruction: the window is one slice inside
# the attend's fusion, which is what that fusion produces
SERVE_SCOPES = ("decode.dispatch", "sample", "embed", "attn.qkv",
                "cache.append", "attn.attend", "attn.out", "mlp", "head")
ADMIT_SCOPES = ("decode.admit", "attn.qkv", "attn.attend", "attn.out",
                "mlp", "head", "sample", "cache.append")


@pytest.fixture
def traced():
    """The process tracer and compile tracker, clean before and after;
    the test switches the tracer on for its own window."""
    tracer, tracker = get_tracer(), xla_stats.get_compile_tracker()
    saved = tracer.enabled, tracer.annotate_device, tracker.enabled
    tracer.enabled = False
    tracker.reset()
    yield tracer
    tracer.enabled, tracer.annotate_device, tracker.enabled = saved
    tracker.reset()


@pytest.fixture
def lowerings():
    """Counts the programs JAX lowers (its own monitoring event)."""
    import jax.monitoring

    seen = []

    def listener(event, duration, **kwargs):
        if event == LOWERED:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listener)


def toy_sweep():
    """A two-layer dense ``build_tick`` train sweep and its operands."""
    specs = [dict(kind="dense", activation=act, leaves=fused._WB_LEAVES,
                  has_params=True, solver="momentum")
             for act in ("tanh", "linear")]
    sweep = fused.build_tick(specs, "none")[2]
    rng = numpy.random.RandomState(0)

    def layer(a, b):
        return {"p": {"w": jnp.asarray(rng.randn(a, b), jnp.float32) * .1,
                      "b": jnp.zeros(b)},
                "v": {"w": jnp.zeros((a, b)), "b": jnp.zeros(b)}}

    hypers = [jnp.asarray([0.01, 0.01, 0.0, 0.0, 0.9], jnp.float32)] * 2
    args = ([layer(12, 16), layer(16, 4)], hypers, {},
            jnp.asarray(rng.randn(32, 12), jnp.float32),
            jnp.asarray(rng.randint(0, 4, 32)),
            numpy.arange(32).reshape(4, 8), numpy.full(4, 8, numpy.int32),
            numpy.float32(32), numpy.zeros(4, numpy.int64))
    return sweep, args


def toy_decoder(slots=2, max_len=32):
    from veles_tpu.parallel.transformer_step import init_transformer_params
    from veles_tpu.serving import ContinuousDecoder

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, 2, 16, 4, 11)
    table = jnp.asarray(rng.randn(11, 16).astype(numpy.float32) * 0.3)
    return ContinuousDecoder(params, table, 4, slots=slots,
                             max_len=max_len, n_tokens=4)


def scopes_held(table):
    return {name for _, op_name in table["instructions"].values()
            for name in xla_stats.scope_names(op_name)}


# -- (a) the table names every scope ------------------------------------

def test_train_sweep_table_names_every_scope_and_tells_backward(traced):
    sweep, args = toy_sweep()
    traced.enabled = True
    sweep(*args)
    traced.enabled = False
    (table,) = xla_stats.scope_table("train_sweep")
    assert table["function"] == "local_train_sweep"
    assert not table["outside_cache"]
    assert set(TRAIN_SCOPES) <= scopes_held(table)
    op_names = [op for _, op in table["instructions"].values()]
    forward = [op for op in op_names if "jvp(fwd)" in op
               and "transpose(" not in op]
    backward = [op for op in op_names if "transpose(jvp(fwd))" in op]
    assert forward and backward
    # both layers have ops on both sides, and an update each
    for layer in ("l0_dense", "l1_dense"):
        assert any(layer in op for op in forward), layer
        assert any(layer in op for op in backward), layer
        assert any("update/%s/" % layer in op for op in op_names), layer


def test_a_fused_instruction_resolves_to_a_scope(traced):
    sweep, args = toy_sweep()
    traced.enabled = True
    sweep(*args)
    traced.enabled = False
    (table,) = xla_stats.scope_table("train_sweep")
    fusions = {name: op for name, (_, op) in table["instructions"].items()
               if "fusion" in name}
    assert fusions
    scoped = [op for op in fusions.values()
              if {"data", "fwd", "update"} & set(xla_stats.scope_names(op))]
    assert len(scoped) >= len(fusions) // 2
    assert any("update" in xla_stats.scope_names(op) for op in scoped)


@pytest.mark.parametrize("function,scopes", [
    ("slot_step_many", SERVE_SCOPES), ("slot_admit_many", ADMIT_SCOPES)])
def test_decode_tables_name_every_scope(traced, function, scopes):
    decoder = toy_decoder()
    traced.enabled = True
    decoder.submit([1, 2, 3])
    decoder.submit([4, 5, 6, 7, 8])
    decoder.drain_pipelined(2)
    traced.enabled = False
    tables = xla_stats.scope_table(function)
    assert tables
    for table in tables:
        assert function in table["function"]
        assert set(scopes) <= scopes_held(table), \
            set(scopes) - scopes_held(table)
        if function == "slot_step_many":
            # no instruction of its own copies a layer's window out
            assert "cache.read" not in scopes_held(table)


def test_decode_table_is_of_the_program_that_ran(traced):
    """The slot state's leaves are committed to their place and the
    table is compiled from shapes alone: both must lower the same
    module, or a traced op finds no row (on the chip that read every
    decode part as unscoped). The state's place is pinned on the
    programs, so what the arrays are committed to changes nothing."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.parallel import decode

    decoder = toy_decoder()
    decoder.submit([1, 2, 3])
    traced.enabled = True
    handle = decoder.dispatch_chunk(2)
    traced.enabled = False
    decoder.collect_chunk(handle)
    (table,) = xla_stats.scope_table("slot_step_many")
    span = decoder._attended_span(2)
    ran = decode.slot_fns(decoder.state)[2].__wrapped__.lower(
        decoder.params, decoder.embed_table, decoder.heads,
        decoder.state, jnp.asarray(decoder._active()), 2,
        jnp.float32(1.0), False, 0, span).compile().as_text()
    assert all(leaf.committed for leaf in jax.tree.leaves(decoder.state))
    assert set(xla_stats.parse_hlo_scopes(ran)) \
        == set(table["instructions"])


def test_one_table_entry_per_distinct_program(traced):
    sweep, args = toy_sweep()
    traced.enabled = True
    for _ in range(3):
        params, _ = sweep(*args)
        args = (params,) + args[1:]
    traced.enabled = False
    assert len(xla_stats.scope_table("train_sweep")) == 1
    assert xla_stats.scope_table("no_such_program") == []


# -- (b) nothing compiles with tracing off or a window open --------------

def test_nothing_is_noted_or_lowered_with_the_tracer_off(traced,
                                                         lowerings):
    sweep, args = toy_sweep()
    params, _ = sweep(*args)            # warm: its own lowering
    del lowerings[:]
    sweep(params, *args[1:])
    assert xla_stats.get_compile_tracker()._programs == {}
    assert xla_stats.scope_table("train_sweep") == []
    assert lowerings == []


def test_an_open_window_notes_and_compiles_nothing(traced, lowerings):
    sweep, args = toy_sweep()
    params, _ = sweep(*args)
    del lowerings[:]
    traced.enabled = True
    sweep(params, *args[1:])
    with pytest.raises(RuntimeError, match="window"):
        xla_stats.scope_table("train_sweep")
    assert lowerings == []
    noted = xla_stats.get_compile_tracker()._programs
    assert len(noted) == 1 and next(iter(noted.values()))[2] is None
    # the operands are kept as shapes, never as buffers
    kept = jax.tree.leaves(next(iter(noted.values()))[1])
    assert kept and not any(isinstance(x, jax.Array) for x in kept)
    traced.enabled = False
    assert len(xla_stats.scope_table("train_sweep")) == 1
    again = len(lowerings)
    xla_stats.scope_table("train_sweep")    # kept: compiled once
    assert len(lowerings) == again


# -- (e) a stale binary in the persistent cache -------------------------

def test_a_binary_cached_before_its_scopes_is_compiled_outside_the_cache(
        traced, tmp_path, monkeypatch):
    """JAX leaves metadata out of the compile cache's key: the program
    without its scopes and with them are ONE entry. Plant the first,
    look the second up: a hit, with the old metadata. The table still
    names every scope, and says that it compiled outside the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()

    def program(scoped):
        # two functions of one name and one HLO (JAX would hand one
        # function's first trace back for the second)
        def planted(x, w):
            if scoped:
                with jax.named_scope("late_scope"):
                    return jnp.tanh(x @ w) * 3.0
            return jnp.tanh(x @ w) * 3.0
        return jax.jit(planted)

    x = jnp.ones((8, 16))
    w = jnp.ones((16, 16))
    try:
        program(False)(x, w).block_until_ready()     # cached, unscoped
        entries = sorted(tmp_path.iterdir())
        assert entries
        late = xla_stats.instrument("planted", program(True))
        traced.enabled = True
        late(x, w).block_until_ready()               # a hit: stale text
        traced.enabled = False
        assert sorted(tmp_path.iterdir()) == entries
        (table,) = xla_stats.scope_table("planted")
        assert table["outside_cache"] is True
        assert "late_scope" in scopes_held(table)
        # a program whose cached binary does carry its scopes is not
        # compiled twice
        tracker = xla_stats.get_compile_tracker()
        tracker.reset()
        fresh = xla_stats.instrument(
            "fresh", jax.jit(lambda x: jax.named_scope("s")(jnp.sin)(x)))
        traced.enabled = True
        fresh(x).block_until_ready()
        traced.enabled = False
        (table,) = xla_stats.scope_table("lambda")
        assert table["outside_cache"] is False
        assert "s" in scopes_held(table)
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


# -- the compiled text's parser -----------------------------------------

HLO = """\
HloModule jit_f, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %constant.1 = f32[] constant(2), metadata={op_name="jit(f)/jit(main)"}
  %broadcast.1 = f32[8]{0} broadcast(%constant.1), dimensions={}, metadata={op_name="jit(f)/jit(main)"}
  %mul.1 = f32[8]{0} multiply(%param_0, %broadcast.1), metadata={op_name="jit(f)/jit(main)/jvp(fwd)/l0/mul" source_line=3}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %mul.1), metadata={op_name="jit(f)/jit(main)/transpose(jvp(fwd))/l0/add_any"}
}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%param_0.1), metadata={op_name="jit(f)/jit(main)/jvp(fwd)/l1/exp"}
  %exp.2 = f32[8]{0} exponential(%exp.1), metadata={op_name="jit(f)/jit(main)/jvp(fwd)/l1/exp"}
  %mul.2 = f32[8]{0} multiply(%exp.2, %param_0.1), metadata={op_name="jit(f)/jit(main)/transpose(jvp(fwd))/l1/mul"}
  %sub.2 = f32[8]{0} subtract(%mul.2, %param_0.1), metadata={op_name="jit(f)/jit(main)/update/l1/sub"}
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%mul.2, f32[8]{0:T(8,128)} %sub.2)
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %sub.3 = f32[8]{0} subtract(%param_0.2, %param_0.2), metadata={op_name="jit(f)/jit(main)/update/l0/sub"}
  %neg.1 = f32[8]{0} negate(%sub.3), metadata={op_name="jit(f)/jit(main)/fwd/l1/neg"}
  %sub.4 = f32[8]{0} subtract(%neg.1, %sub.3), metadata={op_name="jit(f)/jit(main)/update/l0/sub"}
  ROOT %bitcast.1 = f32[8]{0} bitcast(%sub.4)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion = f32[8]{0:T(8)} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/jit(main)/own"}
  %copy.3 = (f32[8]{0:T(8)S(1)}, u32[]{:S(2)}) copy-start(%fusion)
  %fusion.1 = (f32[8]{0}, f32[8]{0}) fusion(%fusion), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.2 = f32[8]{0} fusion(%fusion), kind=kLoop, calls=%fused_computation.2
}
"""


@pytest.mark.parametrize("instruction,shape,op_name", [
    # a fusion is what it produces: the root's scope, not its own
    ("fusion", "f32[8]{0:T(8)}",
     "jit(f)/jit(main)/transpose(jvp(fwd))/l0/add_any"),
    # two outputs, backward and update: the first on a tie, whatever
    # the two forward instructions recomputed inside it would vote
    ("fusion.1", "(f32[8]{0}, f32[8]{0})",
     "jit(f)/jit(main)/transpose(jvp(fwd))/l1/mul"),
    # a root that carries no scope (XLA's bitcast): most of the rest
    ("fusion.2", "f32[8]{0}", "jit(f)/jit(main)/update/l0/sub"),
    # XLA's own instruction, no metadata: reported as it is
    ("copy.3", "(f32[8]{0:T(8)S(1)}, u32[]{:S(2)})", ""),
    ("x", "f32[8]{0}", "x"),
])
def test_parse_hlo_scopes(instruction, shape, op_name):
    table = xla_stats.parse_hlo_scopes(HLO)
    assert table[instruction] == (shape, op_name)
    # instructions of fused computations are folded, not listed
    assert not {"mul.1", "sub.2", "neg.1"} & set(table)


@pytest.mark.parametrize("path,names", [
    ("jit(f)/jit(main)/transpose(jvp(fwd))/l0_conv/mul",
     ["f", "main", "fwd", "l0_conv", "mul"]),
    ("jit(_slot_step_many)/decode.dispatch/while/body/cache.read/slice",
     ["_slot_step_many", "decode.dispatch", "while", "body",
      "cache.read", "slice"]),
    ("", [""]),
])
def test_scope_names_frees_a_name_of_its_transformations(path, names):
    assert xla_stats.scope_names(path) == names


# -- (d) the unit.run.<unit> label --------------------------------------

def every_unit_class():
    import veles_tpu

    for info in pkgutil.walk_packages(veles_tpu.__path__, "veles_tpu."):
        if ".tests" in info.name or info.name.endswith("__main__"):
            continue
        try:
            importlib.import_module(info.name)
        except Exception:       # an optional dependency that is not here
            continue
    seen, todo = set(), [Unit]
    while todo:
        for cls in todo.pop().__subclasses__():
            if cls not in seen:
                seen.add(cls)
                todo.append(cls)
    return sorted(cls.__name__ for cls in seen)


def test_every_unit_class_gets_a_label_the_trace_reduction_reads():
    names = every_unit_class()
    assert len(names) > 40 and "FusedTick" in names
    for name in names:
        assert SPAN_NAME.match(run_span_label(name)), name


@pytest.mark.parametrize("name,label", [
    ("FusedTick", "unit.run.fused_tick"),
    ("All2AllSoftmax", "unit.run.all2_all_softmax"),
    ("GDConv", "unit.run.gd_conv"),
    ("DecisionMSE", "unit.run.decision_mse"),
    ("AlexNet loader #2", "unit.run.alex_net_loader_2"),
    ("fused-identity", "unit.run.fused_identity"),
    ("__", "unit.run.unit"),
    ("Ünit", "unit.run.nit"),
])
def test_run_span_label_snake_cases_odd_names(name, label):
    assert run_span_label(name) == label
    assert SPAN_NAME.match(label)


def test_the_label_names_the_annotation_and_the_event_keeps_unit_run(
        traced, tmp_path, monkeypatch):
    from veles_tpu.core import logger as logger_mod
    from veles_tpu.core.units import TrivialUnit
    from veles_tpu.core.workflow import Workflow

    recorder = EventRecorder()
    recorder.open(str(tmp_path / "events.jsonl"))
    monkeypatch.setattr(logger_mod, "_event_recorder", recorder)
    annotated = []

    class Annotation:
        def __init__(self, name):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    unit = TrivialUnit(Workflow(DummyLauncher(), name="wf"),
                       name="OddName 7")
    traced.enabled = traced.annotate_device = True
    unit._check_gate_and_run(None)
    traced.enabled = traced.annotate_device = False
    recorder.close()
    assert annotated == ["unit.run.odd_name_7"]
    events = [json.loads(line) for line in
              open(str(tmp_path / "events.jsonl"))]
    assert [e["name"] for e in events] == ["unit.run", "unit.run"]
    assert all(e["unit"] == "OddName 7" and e["cls"] == "TrivialUnit"
               and "label" not in e for e in events)


# -- (c) tracing off, the new span sites allocate nothing ---------------

@pytest.fixture
def no_span_may_be_built(monkeypatch, traced):
    def refuse(*args, **kwargs):
        raise AssertionError("a Span was built with the tracer off")

    monkeypatch.setattr(tracing.Span, "__init__", refuse)
    return traced


def small_workflow(max_epochs=2, hidden=8):
    from veles_tpu.models.mlp import MLPWorkflow

    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    rng = numpy.random.RandomState(1)
    return MLPWorkflow(
        DummyLauncher(), layers=(hidden, 4),
        loader_kwargs=dict(
            data=rng.randn(120, 6).astype(numpy.float32),
            labels=rng.randint(0, 4, 120).astype(numpy.int32),
            class_lengths=[0, 40, 80], minibatch_size=20,
            normalization_type="linear"),
        learning_rate=0.1, max_epochs=max_epochs, fused=True,
        fused_sweep=True, name="scopes-mlp")


def test_fused_tick_run_allocates_no_span_when_disabled(
        no_span_may_be_built):
    workflow = small_workflow()
    workflow.initialize()
    workflow.run()
    assert workflow.fused_tick is not None and workflow.fused_tick.ticks
    assert get_tracer().span("engine.train_sweep") is NULL_SPAN


def post(url, payload):
    request = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(request, timeout=60) as response:
        return json.loads(response.read().decode())


def small_api():
    from veles_tpu.parallel.transformer_step import init_transformer_params
    from veles_tpu.serving import GenerateAPI

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, 2, 16, 4, 11)
    table = jnp.asarray(rng.randn(11, 16).astype(numpy.float32) * 0.3)
    return GenerateAPI(params, table, 4, slots=2, max_len=32, n_tokens=4,
                       chunk=2, port=0)


def test_drive_allocates_no_span_when_disabled(no_span_may_be_built):
    api = small_api()
    api.start()
    try:
        body = post("http://127.0.0.1:%d/generate" % api.port,
                    {"tokens": [1, 2, 3]})
        assert len(body["tokens"]) == 4
    finally:
        api.stop()


def test_the_program_writes_its_new_spans_when_tracing(traced, tmp_path,
                                                       monkeypatch):
    """Tracing on, a training run and a served request leave every new
    span name in the event stream (what ``observe export-trace``
    shows); ``unit.run`` stays one name for every unit."""
    from veles_tpu.core import logger as logger_mod

    recorder = EventRecorder()
    recorder.open(str(tmp_path / "events.jsonl"))
    monkeypatch.setattr(logger_mod, "_event_recorder", recorder)
    traced.enabled = True
    workflow = small_workflow()
    workflow.initialize()
    workflow.run()
    api = small_api()
    api.start()
    try:
        post("http://127.0.0.1:%d/generate" % api.port,
             {"tokens": [1, 2, 3]})
    finally:
        api.stop()
        traced.enabled = False
        recorder.close()
    names = {json.loads(line)["name"]
             for line in open(str(tmp_path / "events.jsonl"))}
    assert {"unit.run", "engine.train_sweep", "engine.eval_sweep",
            "engine.write_back", "decision.settle", "loader.serve_sweep",
            "serve.drive_books", "serve.drive_idle", "decode.dispatch",
            "decode.collect"} <= names
    assert not any(name.startswith("unit.run.") for name in names)
    # the hot programs of both were noted for the scope table
    noted = {key[0] for key in
             xla_stats.get_compile_tracker()._programs}
    assert {"local_train_sweep", "local_eval_sweep", "_slot_step_many",
            "_slot_admit_many"} <= noted


def test_a_capture_leaves_the_handlers_wait_out(traced, tmp_path,
                                                monkeypatch):
    """In a profiler capture the annotated spans are the driver's: the
    handler's ``serve.request``, which waits out the whole request,
    would cover every gap the driver leaves. It is still recorded."""
    from veles_tpu.core import logger as logger_mod

    annotated = []

    class Annotation:
        def __init__(self, name):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    recorder = EventRecorder()
    recorder.open(str(tmp_path / "events.jsonl"))
    monkeypatch.setattr(logger_mod, "_event_recorder", recorder)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    api = small_api()
    api.start()
    traced.enabled = traced.annotate_device = True
    try:
        post("http://127.0.0.1:%d/generate" % api.port,
             {"tokens": [1, 2, 3]})
    finally:
        traced.enabled = traced.annotate_device = False
        api.stop()
        recorder.close()
    names = {json.loads(line)["name"]
             for line in open(str(tmp_path / "events.jsonl"))}
    assert "serve.request" in names
    assert "serve.request" not in annotated
    assert {"decode.dispatch", "decode.collect",
            "serve.drive_books"} <= set(annotated)


# -- the repairs ---------------------------------------------------------

def test_the_tick_programs_book_their_compiles(traced):
    """``instrument`` is on the single-device tick's four jits:
    ``veles_xla_compiles_total`` no longer misses the training sweep."""
    tracker = xla_stats.get_compile_tracker()
    tracker.enabled = True
    tracker.estimate_flops = False
    try:
        # a width no other test builds: its programs compile here
        workflow = small_workflow(hidden=9)
        workflow.initialize()
        workflow.run()
    finally:
        tracker.estimate_flops = True
    snapshot = tracker.snapshot()
    assert snapshot["compiles"].get("fused.train_sweep") == 1
    assert snapshot["compiles"].get("fused.eval_sweep") == 1
    assert snapshot["hits"].get("fused.train_sweep", 0) >= 1


@pytest.mark.parametrize("strict", [False, True])
def test_profile_window_strict_reraises_a_failed_start(
        strict, monkeypatch, tmp_path, traced):
    from veles_tpu.observe.profile import profile_window

    def broken(*args, **kwargs):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "trace", broken)
    ran = []
    if strict:
        with pytest.raises(RuntimeError, match="no profiler here"):
            with profile_window(str(tmp_path), strict=True):
                ran.append(1)
        assert ran == []
    else:
        with profile_window(str(tmp_path)) as where:
            ran.append(where)
        assert ran == [None]
    assert not get_tracer().enabled and not get_tracer().annotate_device


def test_a_recorder_nobody_reads_serialises_nothing(monkeypatch):
    from veles_tpu.core import logger as logger_mod

    def refuse(*args, **kwargs):
        raise AssertionError("a span was serialised for nobody")

    recorder = EventRecorder()
    monkeypatch.setattr(logger_mod.json, "dumps", refuse)
    recorder.record(name="x", etype="begin")
    seen = []
    recorder.add_sink(seen.append)
    monkeypatch.undo()
    recorder.record(name="y", etype="begin")
    assert [e["name"] for e in seen] == ["y"]


REWRITTEN = """
HloModule jit_g, entry_computation_layout={(bf16[8,4])->bf16[8,4]}

ENTRY %main.5 (x: bf16[8,4]) -> bf16[8,4] {
  %x = bf16[8,4]{1,0} parameter(0), metadata={op_name="x"}
  %ragged-dot-metadata = (s32[3]{0}, s32[2]{0}) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %get-tuple-element = s32[3]{0} get-tuple-element(%ragged-dot-metadata), index=0
  %ragged-dot-none = bf16[8,4]{1,0} custom-call(%get-tuple-element, %x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %sort.1 = bf16[8,4]{1,0} sort(%x), dimensions={0}, metadata={op_name="sort"}
  %copy.7 = bf16[8,4]{1,0} copy(%x)
  %negate.3 = bf16[8,4]{1,0} negate(%copy.7), metadata={op_name="jit(g)/mlp/neg"}
  ROOT %multiply.2 = bf16[8,4]{1,0} multiply(%ragged-dot-none, %ragged-dot-none), metadata={op_name="jit(g)/mlp/moe.experts/mul"}
}
"""


@pytest.mark.parametrize("instruction,op_name", [
    # the compiler's rewrite left its own name where the scope stood:
    # the instruction is its user's, through the tuple element too
    ("ragged-dot-none", "jit(g)/mlp/moe.experts/mul"),
    ("ragged-dot-metadata", "jit(g)/mlp/moe.experts/mul"),
    # no user carries a scope: it keeps the rewrite's name
    ("sort.1", "sort"),
    # XLA's own instruction without metadata stays unscoped, whoever
    # uses it; a parameter keeps its name
    ("copy.7", ""),
    ("x", "x"),
])
def test_a_rewritten_instruction_takes_its_users_scope(instruction,
                                                       op_name):
    assert xla_stats.parse_hlo_scopes(REWRITTEN)[instruction][1] == op_name
