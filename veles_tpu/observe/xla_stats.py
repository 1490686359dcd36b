"""Device-truth telemetry: XLA compile events, device memory, online MFU.

PR 4 gave the stack host-side metrics and traces; this module closes the
loop on what the COMPILER and the CHIP are actually doing — the
reference VELES made device behavior first-class observable state
(per-device benchmark kernels feeding fleet balancing, ``SURVEY.md``
§2.2), and a production JAX serving stack treats recompilation storms
and HBM pressure as primary SLO signals. Three coordinated parts:

- **compile tracking**: :func:`instrument` wraps a jitted callable; each
  call consults the jit cache size (``fn._cache_size()``) so a growing
  cache books one compile (with its wall seconds and, via
  ``Lowered.cost_analysis()``, the program's FLOPs) and a steady cache
  books one hit. N compiles of the same program name inside a sliding
  window is a *recompilation storm* — warned once per name, counted
  forever (a shape-churning unit silently recompiling every tick is the
  classic way a TPU run loses 100x throughput);
- **device gauges**: :func:`publish_xla_stats` (a scrape-time collector,
  like every other bridge) samples ``device.memory_stats()`` per local
  device — bytes in use, peak, limit. Backends without an allocator
  report (CPU) fall back to live-buffer accounting so the gauge family
  exists everywhere;
- **online MFU**: the tracked FLOPs of a program divided by its
  observed step seconds (:meth:`CompileTracker.observe_step`, fed by
  the serving driver's chunk cadence) against the device's published
  bf16 peak — ``veles_mfu_ratio{program=...}`` on ``/metrics``, live,
  not just in bench runs.

Everything is disabled by default with the same structurally-no-op
contract as the registry: an instrumented callable costs one attribute
check until a ``/metrics`` surface is mounted
(:func:`ensure_registered`, called by ``core/httpd.py``).
"""

import logging
import re
import threading
import time
from collections import deque

#: published peak dense-matmul throughput per chip (TFLOP/s), bf16 — the
#: MXU's native precision and the honest MFU ceiling — keyed by the
#: EXACT ``device_kind`` string JAX reports, each row with its source.
#: Exact keys on purpose: substring matching let "TPU v5" (the v5p)
#: claim any unknown v5 kind. Every offline reader of a peak and the
#: online MFU gauge share THIS one table.
PEAK_BF16_TFLOPS = {
    "TPU v4 lite": (138.0, "Jouppi et al. 2021, 'Ten Lessons' (TPUv4i)"),
    "TPU v4": (275.0, "Google Cloud documentation, 'TPU v4'"),
    "TPU v5 lite": (197.0, "Google Cloud documentation, 'TPU v5e'"),
    "TPU v5e": (197.0, "Google Cloud documentation, 'TPU v5e'"),
    "TPU v5p": (459.0, "Google Cloud documentation, 'TPU v5p'"),
    "TPU v5": (459.0, "Google Cloud documentation, 'TPU v5p'"),
    "TPU v6 lite": (918.0, "Google Cloud documentation, 'TPU v6e'"),
    "TPU v6e": (918.0, "Google Cloud documentation, 'TPU v6e'"),
}

#: device.memory_stats() keys re-published as gauges (when present)
_MEMORY_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                "largest_alloc_size")


def peak_tflops(device_kind=None):
    """The bf16 peak for ``device_kind`` (default: the first local
    device). ``root.common.observe.peak_tflops`` overrides the table
    (an unlisted chip, or a CPU test run that wants a deterministic MFU
    denominator). Without the override: a ``device_kind`` the table
    lists EXACTLY gives its row; on platform ``tpu`` an unlisted kind
    raises — a device that is not in the table is an error, not a
    missing key; off the TPU there is no peak (None)."""
    from veles_tpu.core.config import root

    override = root.common.observe.get("peak_tflops", None)
    if override:
        return float(override)
    if device_kind is None:
        import jax
        device = jax.devices()[0]
        if device.platform != "tpu":
            return None
        device_kind = device.device_kind
    row = PEAK_BF16_TFLOPS.get(str(device_kind))
    if row is None:
        raise LookupError(
            "no bf16 peak on record for TPU device_kind %r: add a row "
            "(with its source) to observe.xla_stats.PEAK_BF16_TFLOPS or "
            "set root.common.observe.peak_tflops" % (device_kind,))
    return row[0]


def abstractify(args, kwargs):
    """Shape/dtype skeletons of a call's operands: arrays (or tracers)
    become ``ShapeDtypeStruct``, everything else passes through — what
    ``fn.lower`` needs to cost a program without touching (possibly
    donated-and-deleted) buffers."""
    import jax

    def conv(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            # keep the operand's sharding: a layout-pinned program
            # (sharded slot serving) must be costed from the SPMD
            # lowering it actually runs, and a lowering without input
            # shardings can't honor donation against pinned
            # out_shardings (spurious donated-buffer warnings).
            # ...except SingleDeviceSharding: a replicated operand of
            # a shard_map program (the fused fleet tick's params)
            # carries one, and pinning THAT into the lower fails with
            # "incompatible devices" against the mesh — dropping it
            # lets the lowering re-infer placement. Scoped by TYPE,
            # not device count: a NamedSharding over a 1-device serve
            # mesh must keep costing from its real SPMD lowering
            sharding = getattr(x, "sharding", None)
            single = getattr(jax.sharding, "SingleDeviceSharding",
                             None)
            if single is not None and isinstance(sharding, single):
                sharding = None
            # weak_type rides along: a weakly typed operand lowers to
            # another program than its strong twin, and the scope
            # table (below) must find the one that ran
            weak = bool(getattr(x, "weak_type", False))
            try:
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=sharding,
                                            weak_type=weak)
            except (TypeError, ValueError):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            weak_type=weak)
        return x

    return (jax.tree.map(conv, args), jax.tree.map(conv, kwargs))


def program_flops(fn, *args, **kwargs):
    """FLOPs of ``fn``'s program for these operand shapes via
    ``Lowered.cost_analysis()`` (no XLA compile — the lowering is a
    trace). None when the backend/version can't say."""
    try:
        a_args, a_kwargs = abstractify(args, kwargs)
        analysis = fn.lower(*a_args, **a_kwargs).cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        flops = analysis.get("flops")
        return float(flops) if flops and flops > 0 else None
    except Exception:
        return None


class CompileTracker:
    """Thread-safe per-program compile/hit/storm/FLOPs/step bookkeeping.

    Disabled (the default) the instrumented call sites cost one
    attribute check. Enabled, each call pays one cheap C-level
    ``_cache_size()`` read plus a lock on the (rare) compile path."""

    #: a storm = this many compiles of the SAME program name...
    STORM_THRESHOLD = 5
    #: ...within this sliding window (seconds)
    STORM_WINDOW = 60.0
    #: step-seconds EMA weight of the newest observation
    STEP_EMA = 0.2

    def __init__(self, enabled=False):
        self.enabled = enabled
        #: compute program FLOPs (one extra trace) at each compile;
        #: operators can turn it off for huge graphs
        self.estimate_flops = True
        self._lock = threading.Lock()
        self._compiles = {}         # name -> count
        self._compile_seconds = {}  # name -> total wall seconds
        self._hits = {}             # name -> count
        self._storms = {}           # name -> storm count
        self._stamps = {}           # name -> deque of recent stamps
        self._storm_warned = set()
        self._flops = {}            # name -> latest program FLOPs
        self._step_ema = {}         # name -> EMA of step seconds
        self._step_count = {}       # name -> observations
        #: recent compile windows (name, start_mono, end_mono) — the
        #: request ledger intersects these with a request's lifetime
        #: to attribute a latency spike to the compile that caused it
        self._windows = deque(maxlen=256)
        #: programs dispatched while the tracer was on (note_program):
        #: signature -> [jitted fn, abstract operands, scope table]
        self._programs = {}

    def enable(self):
        self.enabled = True
        return self

    def disable(self):
        self.enabled = False
        return self

    def reset(self):
        """Drop all state (test isolation); keeps the enabled flag."""
        with self._lock:
            for store in (self._compiles, self._compile_seconds,
                          self._hits, self._storms, self._stamps,
                          self._flops, self._step_ema,
                          self._step_count):
                store.clear()
            self._storm_warned.clear()
            self._programs.clear()

    # -- recording --------------------------------------------------------
    def record_compile(self, name, seconds, flops=None):
        warn = False
        with self._lock:
            self._compiles[name] = self._compiles.get(name, 0) + 1
            self._compile_seconds[name] = \
                self._compile_seconds.get(name, 0.0) + float(seconds)
            if flops:
                self._flops[name] = float(flops)
            stamps = self._stamps.get(name)
            if stamps is None:
                stamps = self._stamps[name] = deque(
                    maxlen=self.STORM_THRESHOLD)
            now = time.monotonic()
            self._windows.append((name, now - float(seconds), now))
            stamps.append(now)
            if len(stamps) == self.STORM_THRESHOLD \
                    and now - stamps[0] <= self.STORM_WINDOW:
                self._storms[name] = self._storms.get(name, 0) + 1
                stamps.clear()  # re-arm: count whole storms, not tails
                warn = name not in self._storm_warned
                self._storm_warned.add(name)
        if warn:
            logging.getLogger("CompileTracker").warning(
                "recompilation storm: %r compiled %d times within %.0fs "
                "— a churning shape is defeating the jit cache "
                "(reported once per program; veles_xla_recompile_"
                "storms_total keeps counting)",
                name, self.STORM_THRESHOLD, self.STORM_WINDOW)

    def record_hit(self, name):
        with self._lock:
            self._hits[name] = self._hits.get(name, 0) + 1

    def observe_step(self, name, seconds):
        """Feed one measured step wall time for ``name`` (the serving
        driver's chunk cadence); the MFU gauge divides the program's
        FLOPs by this EMA."""
        seconds = float(seconds)
        if seconds <= 0:
            return
        with self._lock:
            ema = self._step_ema.get(name)
            self._step_ema[name] = seconds if ema is None else (
                (1 - self.STEP_EMA) * ema + self.STEP_EMA * seconds)
            self._step_count[name] = self._step_count.get(name, 0) + 1

    def compiles_overlapping(self, t0, t1):
        """Compile windows intersecting the monotonic interval
        ``[t0, t1]`` as ``[(program, overlap_seconds)]`` — how the
        request ledger names the compile stall that stretched a
        request (``observe/reqledger.py``)."""
        with self._lock:
            windows = list(self._windows)
        out = []
        for name, start, end in windows:
            overlap = min(end, t1) - max(start, t0)
            if overlap > 0:
                out.append((name, overlap))
        return out

    def set_program_flops(self, name, flops):
        """Pin a program's FLOPs explicitly (callers with analytic
        counts, e.g. the bench's model formulas)."""
        if flops and flops > 0:
            with self._lock:
                self._flops[name] = float(flops)

    # -- views ------------------------------------------------------------
    def storm_total(self):
        """Total recompilation storms across programs — the serving
        governor's stall predictor (one lock, no device/peak lookups:
        cheap enough for a per-tick control-loop read)."""
        with self._lock:
            return sum(self._storms.values())

    def snapshot(self):
        """Plain-dict view for the web-status dashboard and black-box
        dumps."""
        # peak lookup OUTSIDE the lock: it can touch jax.devices()
        # (backend init takes seconds cold) and every instrumented
        # hot-path call would queue behind it
        peak = peak_tflops()
        with self._lock:
            mfu = {}
            for name, flops in self._flops.items():
                ema = self._step_ema.get(name)
                if ema:
                    fps = flops / ema
                    mfu[name] = {"flops_per_sec": fps}
                    if peak:
                        mfu[name]["mfu"] = fps / (peak * 1e12)
            return {"compiles": dict(self._compiles),
                    "compile_seconds": {
                        k: round(v, 4)
                        for k, v in self._compile_seconds.items()},
                    "hits": dict(self._hits),
                    "storms": dict(self._storms),
                    "flops": dict(self._flops),
                    "mfu": mfu}

    def publish(self, registry):
        """Scrape-time re-publication into ``registry`` (the bridge
        contract: the tracker stays the source of truth)."""
        with self._lock:
            compiles = dict(self._compiles)
            seconds = dict(self._compile_seconds)
            hits = dict(self._hits)
            storms = dict(self._storms)
            flops = dict(self._flops)
            step_ema = dict(self._step_ema)
        for name, count in compiles.items():
            registry.counter_set(
                "veles_xla_compiles_total", count,
                labels={"program": name},
                help="XLA compiles per instrumented program")
        for name, total in seconds.items():
            registry.counter_set(
                "veles_xla_compile_seconds_total", round(total, 6),
                labels={"program": name},
                help="wall seconds spent compiling per program")
        for name, count in hits.items():
            registry.counter_set(
                "veles_xla_cache_hits_total", count,
                labels={"program": name},
                help="jit cache hits per instrumented program")
        for name, count in storms.items():
            registry.counter_set(
                "veles_xla_recompile_storms_total", count,
                labels={"program": name},
                help="recompilation storms (N same-name compiles in a "
                     "sliding window)")
        peak = peak_tflops()
        for name, value in flops.items():
            registry.set("veles_xla_program_flops", value,
                         labels={"program": name},
                         help="cost_analysis FLOPs of the latest "
                              "compiled program")
            ema = step_ema.get(name)
            if ema:
                fps = value / ema
                registry.set(
                    "veles_program_flops_per_second", fps,
                    labels={"program": name},
                    help="program FLOPs over the measured step-time EMA")
                if peak:
                    registry.set(
                        "veles_mfu_ratio", fps / (peak * 1e12),
                        labels={"program": name},
                        help="model FLOPs utilization vs the device "
                             "bf16 peak")


_tracker = CompileTracker(enabled=False)


def get_compile_tracker():
    return _tracker


def instrument(name, fn):
    """Wrap a jitted callable so compiles/hits book into the process
    tracker under ``name``. Disabled-tracker calls delegate after one
    attribute check; callables without a ``_cache_size`` introspection
    hook (non-jit objects, older jax) are returned unwrapped."""
    import functools

    from veles_tpu.observe.tracing import get_tracer

    tracker = get_compile_tracker()
    tracer = get_tracer()
    cache_size = getattr(fn, "_cache_size", None)
    if cache_size is None:
        return fn

    @functools.wraps(fn, assigned=("__doc__",), updated=())
    def wrapper(*args, **kwargs):
        if tracer.enabled:
            # a traced window is open: remember WHICH program this
            # dispatch runs, so that scope_table() can name its
            # instructions once the window has closed
            note_program(fn, args, kwargs)
        if not tracker.enabled:
            return fn(*args, **kwargs)
        before = cache_size()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        if cache_size() > before:
            flops = (program_flops(fn, *args, **kwargs)
                     if tracker.estimate_flops else None)
            tracker.record_compile(name, time.perf_counter() - t0,
                                   flops=flops)
        else:
            tracker.record_hit(name)
        return out

    wrapper.__wrapped__ = fn
    wrapper.program_name = name
    return wrapper


# -- the scope table ---------------------------------------------------------
#
# A jax.profiler capture names a device op by its instruction's text
# and carries no ``op_name``, so the program says itself which
# instruction belongs to which ``jax.named_scope``: ``instrument``
# notes the programs dispatched while the tracer is on (shapes only),
# and :func:`scope_table` reads the metadata out of their compiled
# text once the window has closed.

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[^\s(]+) .*\{$")
_HLO_INSTRUCTION = re.compile(
    r"^\s+(?P<root>ROOT )?%?(?P<name>\S+) = (?P<shape>.+?) "
    r"(?P<opcode>[a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="(?P<op_name>[^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?(?P<callee>[^\s,{}]+)")
_HLO_OPERAND = re.compile(r"%?([A-Za-z_][\w.\-]*)\s*(?:,|$)")
#: instructions that compute nothing: a constant and its broadcast
#: carry the enclosing call's ``op_name``, not a scope's, and would
#: outvote the instructions that do a fusion's work
_NO_VOTE = ("parameter", "constant", "broadcast", "iota", "bitcast",
            "tuple", "get-tuple-element")
_TRANSFORMED = re.compile(r"^(?:[A-Za-z_]\w*\()+(?P<inner>[^()]*)\)+$")


def note_program(fn, args, kwargs):
    """Remember the program that ``fn(*args, **kwargs)`` dispatches:
    the jitted callable and its operands' shape/dtype skeletons
    (:func:`abstractify`; static operands as they are). A set: one
    entry per distinct program, whatever the number of dispatches."""
    import jax

    leaves, treedef = jax.tree.flatten((args, kwargs))
    try:
        key = (getattr(fn, "__name__", None) or repr(fn), treedef, tuple(
            (x.shape, x.dtype, bool(getattr(x, "weak_type", False)))
            if hasattr(x, "shape") and hasattr(x, "dtype") else x
            for x in leaves))
        programs = get_compile_tracker()._programs
        if key not in programs:
            programs[key] = [fn, abstractify(args, kwargs), None]
    except TypeError:
        # an unhashable static operand: not a program this can name
        pass


def _operands(rest):
    """The operand names of an instruction, from the text after its
    opening bracket up to the bracket that closes it."""
    depth = 1
    for i, char in enumerate(rest):
        depth += (char == "(") - (char == ")")
        if not depth:
            return _HLO_OPERAND.findall(rest[:i])
    return []


def scope_names(path):
    """The names along an ``op_name`` (or a name stack) as a list,
    each freed of the transformations JAX wraps round it:
    ``"jit(f)/transpose(jvp(fwd))/l0_conv/mul"`` -> ``["f", "fwd",
    "l0_conv", "mul"]``."""
    out = []
    for part in path.split("/"):
        found = _TRANSFORMED.match(part)
        out.append(found.group("inner") if found else part)
    return out


def parse_hlo_scopes(text):
    """``{instruction name: (output shape text, op_name)}`` of every
    instruction of a compiled module's text that can run as an op of
    its own (those of fused computations are folded into their
    fusion). An instruction that calls a computation (a fusion, an
    asynchronous wrapper) is what that computation produces: it gets
    the ``op_name`` whose scope (the ``op_name`` less its last part)
    most of the root's instructions carry, the root being one
    instruction or, for several outputs, the operands of its tuple.
    Where the root carries none (a bitcast, a copy XLA put there) the
    vote goes to all of the computation's instructions that compute
    (not its parameters, constants and broadcasts: they carry the
    enclosing call's ``op_name``), and where none of those does
    either the caller keeps its own. An instruction that a rewrite of
    the compiler's made carries the rewrite's name and no scope
    (``ragged-dot-none``: the grouped products of ``ops/moe.py``;
    ``gather``, ``sort``): it takes the ``op_name`` of the first
    instruction that uses what it makes, looked for through other such
    instructions and through those without metadata. An instruction
    without metadata has ``op_name`` "", as before."""
    computations, current = {}, None
    for line in text.splitlines():
        if current is None:
            head = _HLO_COMPUTATION.match(line)
            if head and " -> " in line:
                current = computations.setdefault(head.group("name"), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        found = _HLO_INSTRUCTION.match(line)
        if not found:
            continue
        op_name = _HLO_OP_NAME.search(line)
        calls = _HLO_CALLS.search(line)
        operands = _operands(line[found.end():])
        current.append((found.group("name"), found.group("shape"),
                        op_name.group("op_name") if op_name else "",
                        calls.group("callee") if calls else None,
                        found.group("opcode"), operands,
                        bool(found.group("root"))))
    called = {row[3] for rows in computations.values() for row in rows
              if row[3]}

    def folded(callee, own, depth=0):
        rows = computations.get(callee, ())
        if not rows or depth > 8:
            return own
        root = next((row for row in rows if row[6]), rows[-1])
        produced = [row for row in rows if row[0] in root[5]] \
            if root[4] == "tuple" else [root]
        for voters in (produced, rows):
            votes = {}
            for _, _, op_name, inner, opcode, _, _ in voters:
                if inner:
                    op_name = folded(inner, op_name, depth + 1)
                if op_name and opcode not in _NO_VOTE:
                    votes.setdefault(op_name.rpartition("/")[0],
                                     []).append(op_name)
            if votes:
                # the most votes; the first seen on a tie
                return max(votes.values(), key=len)[0]
        return own

    table = {}
    for name, rows in computations.items():
        if name in called:
            continue
        own = {row[0]: folded(row[3], row[2]) if row[3] else row[2]
               for row in rows}
        users = {}
        for row in rows:
            for operand in row[5]:
                users.setdefault(operand, []).append(row[0])

        def scoped(instruction, depth=0):
            if "/" in own[instruction] or depth > 6:
                return own[instruction]
            for user in users.get(instruction, ()):
                found = scoped(user, depth + 1)
                if "/" in found:
                    return found
            return own[instruction]

        for row in rows:
            # a rewrite's name is there and holds no scope; parameters
            # carry their own names, and what carries nothing stays so
            rewritten = own[row[0]] and "/" not in own[row[0]] \
                and row[4] != "parameter"
            table[row[0]] = (row[1], scoped(row[0]) if rewritten
                             else own[row[0]])
    return table


def _written_scopes(jaxpr, out=None):
    """The names the program's source puts on its name stack (the
    ``jax.named_scope``s, freed of ``jvp(...)`` and the like), read
    off the traced jaxpr and every jaxpr inside it."""
    out = set() if out is None else out
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        stack = str(eqn.source_info.name_stack)
        if stack:
            out.update(scope_names(stack))
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else (value,)):
                if hasattr(getattr(inner, "jaxpr", inner), "eqns"):
                    _written_scopes(inner, out)
    return out


def _compiled_outside_cache(lowered):
    """Compile ``lowered`` anew, past both of JAX's caches: the
    persistent one is switched off for the call, and the in-process
    one (keyed by the module and its options) is missed by naming a
    debug option at the value it has anyway, which leaves XLA's flags,
    and so its instruction names, as they were."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile(
            compiler_options={"xla_dump_hlo_as_text": False})
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


def _program_scopes(fn, operands):
    """(``parse_hlo_scopes`` of the program's compiled text, whether
    it had to be compiled outside the persistent cache)."""
    traced = fn.trace(*operands[0], **operands[1])
    written = _written_scopes(traced.jaxpr)
    lowered = traced.lower()
    text = lowered.compile().as_text()
    held = {name for op_name in _HLO_OP_NAME.findall(text)
            for name in scope_names(op_name)}
    # "fewer than half", not "none": a binary cached when the program
    # had some of today's scopes is as stale, and XLA may rightly lose
    # a scope or two (folded to a constant)
    stale = 2 * len(written & held) < len(written)
    if stale:
        text = _compiled_outside_cache(lowered).as_text()
    return parse_hlo_scopes(text), stale


def scope_table(function_name):
    """The scope of every instruction of the programs noted while the
    tracer was on whose function's name holds ``function_name``: a
    list of ``{"function", "instructions": {instruction name: (output
    shape text, op_name)}, "outside_cache", "seconds"}``, one per
    distinct program (several share a name: one ``slot_step_many``
    per attended span; a reader matches a traced module to the entry
    whose instruction names and output shapes cover the ops that
    ran). Each program is traced, lowered and compiled here, once,
    and never while the tracer is on. The compile is a hit in JAX's
    persistent cache, so the text read is that of the binary that
    ran, with one exception: JAX leaves metadata out of the cache's
    key, so a binary cached from the same HLO before its scopes
    existed (or under other names) is a hit and carries the old
    metadata. Where the compiled text holds fewer than half of the
    names the source writes, the program is compiled once more with
    the cache switched off (XLA names instructions the same way for
    one input and one set of flags) and ``outside_cache`` says so."""
    from veles_tpu.observe.tracing import get_tracer

    if get_tracer().enabled:
        raise RuntimeError("scope_table() compiles; call it once the "
                           "traced window has closed")
    out = []
    programs = get_compile_tracker()._programs
    for key, entry in list(programs.items()):
        if function_name not in key[0]:
            continue
        if entry[2] is None:
            t0 = time.perf_counter()
            try:
                instructions, stale = _program_scopes(*entry[:2])
            except Exception:
                # diagnostics must not fail the run they describe: a
                # program that cannot be compiled again has no table,
                # and a reader counts its modules as unscoped
                logging.getLogger("xla_stats").exception(
                    "no scope table for %s", key[0])
                instructions, stale = {}, False
            entry[2] = {"function": key[0],
                        "instructions": instructions,
                        "outside_cache": stale,
                        "seconds": time.perf_counter() - t0}
        out.append(entry[2])
    return out


# -- device gauges ----------------------------------------------------------

def _live_bytes_by_device():
    """Fallback memory accounting for backends without an allocator
    report (CPU): sum the live jax buffers per device. A sharded
    array's bytes split evenly over its devices."""
    out = {}
    try:
        import jax
        for arr in jax.live_arrays():
            try:
                devs = list(arr.devices())
                share = arr.nbytes / max(1, len(devs))
                for dev in devs:
                    out[dev.id] = out.get(dev.id, 0) + share
            except Exception:
                continue
    except Exception:
        return {}
    return out


def _sample_device_memory():
    """One pass over the local devices: ``{device_id: stats_dict}``
    with ``memory_stats()`` keys where the backend reports them, or a
    ``{"live_bytes": n}`` fallback (CPU has no allocator report). ONE
    copy of the sampling loop for the gauges, the dashboard summary
    and the black box."""
    out = {}
    try:
        import jax
        devices = jax.local_devices()
    except Exception:
        return out
    live = None
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats:
            out[dev.id] = {key: stats[key] for key in _MEMORY_KEYS
                           if stats.get(key) is not None}
        else:
            if live is None:
                live = _live_bytes_by_device()
            out[dev.id] = {"live_bytes": int(live.get(dev.id, 0))}
    return out


def publish_device_stats(registry):
    """Per-device memory gauges at scrape time. TPU/GPU backends report
    through ``memory_stats()``; CPU falls back to live-buffer bytes so
    ``veles_device_memory_bytes`` exists on every backend."""
    for dev_id, stats in _sample_device_memory().items():
        for kind, value in stats.items():
            registry.set(
                "veles_device_memory_bytes", value,
                labels={"device": str(dev_id), "kind": kind},
                help="device allocator stats per local device")
        # the allocator budget as its own gauge, so dashboards render
        # headroom fraction without digging bytes_limit out of the
        # per-kind stats rows
        if stats.get("bytes_limit"):
            registry.set(
                "veles_device_memory_limit_bytes", stats["bytes_limit"],
                labels={"device": str(dev_id)},
                help="device allocator byte budget per local device")
    peak = peak_tflops()
    if peak:
        registry.set("veles_device_peak_bf16_tflops", peak,
                     help="published bf16 peak of the bench device")
    # the active mesh shape (parallel/mesh.py): which pod layout this
    # process computes under — scraped beside the memory gauges so a
    # fleet dashboard can tell a dp8 slave from a tp8 serving replica
    from veles_tpu.parallel.mesh import active_mesh_info
    mesh = active_mesh_info()
    if mesh:
        for axis, size in mesh["axes"].items():
            registry.set("veles_mesh_axis_size", size,
                         labels={"axis": axis},
                         help="active device-mesh axis sizes")
        registry.set("veles_mesh_devices", mesh["devices"],
                     help="devices spanned by the active mesh")


def publish_xla_stats(registry):
    """The full device-truth collector: compile/hit/storm counters, MFU
    and memory gauges, the in-program fleet-reduce plane
    (``parallel/mapreduce.py``: reduce steps/bytes per precision tier
    and the chip-idle-fraction gauge), and the AOT artifact plane
    (``veles_tpu/aot/loader.py``: loaded programs + hit/miss tallies —
    the flat ``veles_xla_compiles_total`` twin that proves zero
    retrace) — registered once per registry by
    :func:`ensure_registered`, so every ``/metrics`` mount and every
    fleet slave's piggybacked snapshot carries it."""
    get_compile_tracker().publish(registry)
    publish_device_stats(registry)
    from veles_tpu.parallel.mapreduce import publish_reduce_stats
    publish_reduce_stats(registry)
    from veles_tpu.aot.loader import publish_aot_stats
    publish_aot_stats(registry)
    from veles_tpu.observe.memscope import publish_memscope
    publish_memscope(registry)


def ensure_registered(registry=None):
    """Idempotently attach the device-truth collector to ``registry``
    (default: the process-global one) and enable the tracker — called
    by every ``/metrics`` mount (``core/httpd.py``), so processes that
    never serve HTTP keep the disabled fast path."""
    from veles_tpu.observe.metrics import get_metrics_registry

    if registry is None:
        registry = get_metrics_registry()
    tracker = get_compile_tracker()
    tracker.enabled = True
    collector = getattr(registry, "_xla_stats_collector", None)
    if collector is None:
        def collector():
            publish_xla_stats(registry)
        registry._xla_stats_collector = collector
    # registry.reset() (test isolation) clears collectors, so membership
    # is re-checked per mount rather than remembered
    if collector not in registry._collectors:
        registry.add_collector(collector)
    return registry


def device_summary():
    """One compact dict for the web-status dashboard: memory per
    device, compile totals, storms, the best live MFU."""
    snap = get_compile_tracker().snapshot()
    memory = {}
    for dev_id, stats in _sample_device_memory().items():
        if stats.get("bytes_in_use") is not None:
            memory[str(dev_id)] = {
                "bytes_in_use": stats.get("bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit")}
    mfu = None
    for entry in snap["mfu"].values():
        ratio = entry.get("mfu")
        if ratio is not None and (mfu is None or ratio > mfu):
            mfu = ratio
    from veles_tpu.parallel.mesh import mesh_shape_label
    return {"memory": memory,
            "compiles": sum(snap["compiles"].values()),
            "compile_seconds": round(
                sum(snap["compile_seconds"].values()), 3),
            "storms": sum(snap["storms"].values()),
            "mesh": mesh_shape_label(),
            "mfu": round(mfu, 4) if mfu is not None else None}


def format_device_stats(device):
    """A ``device_summary()`` dict as one dashboard table cell (the
    device twin of ``format_serving_health``); empty for masters that
    report none."""
    if not isinstance(device, dict):
        return ""
    parts = []
    memory = device.get("memory")
    if isinstance(memory, dict) and memory:
        used = sum(m.get("bytes_in_use") or 0 for m in memory.values()
                   if isinstance(m, dict))
        limit = sum(m.get("bytes_limit") or 0 for m in memory.values()
                    if isinstance(m, dict))
        if limit:
            parts.append("hbm %.1f/%.1f GiB"
                         % (used / 2 ** 30, limit / 2 ** 30))
        elif used:
            parts.append("hbm %.1f GiB" % (used / 2 ** 30))
    mesh = device.get("mesh")
    if mesh:
        parts.append("mesh %s" % mesh)
    compiles = device.get("compiles")
    if compiles:
        parts.append("%d compiles (%.1fs)"
                     % (compiles, device.get("compile_seconds") or 0.0))
    storms = device.get("storms")
    if storms:
        parts.append("%d RECOMPILE STORMS" % storms)
    mfu = device.get("mfu")
    if mfu is not None:
        parts.append("mfu %.2f" % mfu)
    return " · ".join(parts)
