"""Compiler-visible fleet aggregation: mapreduce primitives in XLA.

The reference VELES merged data-parallel updates on the HOST: every
gradient rode an asyncio frame to the master and was applied under a
lock (``fleet/server.py``), so the chip idled through every reduce.
This module re-expresses that aggregation as *in-program* mapreduce
primitives per DrJAX (*Scalable and Differentiable MapReduce Primitives
in JAX*, PAPERS.md, arxiv 2403.07128): ``broadcast`` / ``map_fn`` /
``reduce_sum`` / ``reduce_mean`` over the named ``"data"`` mesh axis
under ``parallel/mesh.shard_map``, so the whole data-parallel train
step — forward, backward, gradient merge, update — is ONE compiled XLA
program with the reduce riding ICI collectives. Zero host round trips
per step; the fleet wire protocol shrinks to a control plane
(``docs/compiler_fleet.md``).

Reduce precision tiers (``root.common.fleet.reduce``):

- ``f32`` (default) — a plain ``lax.psum``; bit-identical to the
  pre-existing pod-mode gradient merge;
- ``bf16`` — gradients cast to bfloat16 for the wire, summed by the
  collective, widened back: half the bytes of f32;
- ``int8`` — two-stage quantized all-reduce with **per-leaf scales**
  (the ROADMAP item 3 follow-on): a global per-leaf scale (``pmax`` of
  the local amax) quantizes the gradient to int8, an ``all_to_all``
  exchanges chunk shards (each device exactly-sums its chunk in int32),
  and a second global-scale int8 ``all_gather`` replicates the reduced
  tensor — ~4x fewer wire bytes than f32, ~2x fewer than bf16, fully
  deterministic (every device runs the same program on the same bytes,
  so replicas stay in lockstep). Convergence differs from the exact sum
  by two bounded rounding stages; ``tests/test_mapreduce.py`` pins the
  error bound and the loss-curve parity vs the bf16 tier.

Byte accounting follows ``parallel/reshard.py``'s convention (total
bytes on the wire across ALL devices): a ring all-reduce of an
``E``-element tensor moves ``2*(n-1)*E*itemsize`` bytes; the int8 tier
moves ``(n-1)*E`` (all_to_all) + ``(n-1)*E`` (all_gather) int8 bytes
plus two scalar ``pmax`` rounds per leaf.

Observability: :func:`fleet_train_step` instruments the compiled steps
under ``observe/xla_stats`` (program ``mapreduce.fleet_*``) so
``veles_mfu_ratio`` during distributed training is a device-truth
number, and books per-step wire bytes / step cadence into
:class:`ReduceStats` — published on every ``/metrics`` mount as
``veles_fleet_reduce_bytes_total`` / ``veles_fleet_reduce_seconds`` /
``veles_fleet_chip_idle_fraction`` via the ``xla_stats`` collector.
"""

import threading
import time

import numpy

import jax
import jax.numpy as jnp
from jax import lax

from veles_tpu.parallel.mesh import shard_map

#: valid in-program gradient-reduce precisions
REDUCE_PRECISIONS = ("f32", "bf16", "int8")

#: int8 quantization range (symmetric)
_Q_MAX = 127.0

#: a gap this long between steps re-arms the idle-fraction window (a
#: training lull must not be booked as chip idleness — same doctrine as
#: the MFU cadence reset in observe/xla_stats)
CADENCE_RESET = 60.0


def reduce_precision_of(value=None):
    """Validate/resolve the configured reduce tier
    (``root.common.fleet.reduce``); raises naming the knob."""
    if value is None:
        from veles_tpu.core.config import root
        value = root.common.fleet.get("reduce", "f32")
    if value not in REDUCE_PRECISIONS:
        raise ValueError(
            "root.common.fleet.reduce / --fleet-reduce must be one of "
            "%s, got %r" % ("/".join(REDUCE_PRECISIONS), value))
    return value


# -- primitives ---------------------------------------------------------------

def broadcast(tree):
    """DrJAX ``broadcast``: place a server (host) value on every client
    shard. Under the SPMD formulation replication is expressed by the
    ``P()`` in_spec at the :func:`map_fn` boundary, so inside the
    program this is the identity — kept as an explicit primitive so
    fleet step code reads as mapreduce, not as sharding trivia."""
    return tree


def map_fn(fn, mesh, in_specs, out_specs):
    """DrJAX ``map_fn``: run ``fn`` per shard of the ``"data"`` axis.
    A thin delegate to :func:`parallel.mesh.shard_map` (one shard_map
    implementation for the whole tree)."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs)


def _int8_allreduce_leaf(x, axis):
    """Two-stage quantized all-reduce of one full-size leaf (see module
    docstring). Exact int32 accumulation between the two rounding
    stages; both scales are global (``pmax``), so every device computes
    identical bytes and the result is replicated by construction."""
    n = lax.axis_size(axis)
    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    size = flat.size
    pad = (-size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    # stage 1: global per-leaf scale, int8 quantize, chunk exchange
    amax = lax.pmax(jnp.max(jnp.abs(flat)), axis)
    scale = jnp.maximum(amax, jnp.float32(1e-30)) / _Q_MAX
    quant = jnp.clip(jnp.round(flat / scale), -_Q_MAX, _Q_MAX) \
        .astype(jnp.int8)
    chunks = quant.reshape(n, -1)
    # device i ends with every peer's chunk i: (n, chunk) int8
    peers = lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0,
                           tiled=False)
    # exact integer accumulation (int8 sums over n would overflow)
    reduced = peers.astype(jnp.int32).sum(axis=0).astype(jnp.float32) \
        * scale
    # stage 2: re-quantize the reduced chunk with a fresh global scale
    # and replicate it — (n-1)/n int8 bytes instead of f32's 4x
    amax2 = lax.pmax(jnp.max(jnp.abs(reduced)), axis)
    scale2 = jnp.maximum(amax2, jnp.float32(1e-30)) / _Q_MAX
    quant2 = jnp.clip(jnp.round(reduced / scale2), -_Q_MAX, _Q_MAX) \
        .astype(jnp.int8)
    gathered = lax.all_gather(quant2, axis, axis=0, tiled=True)
    out = gathered.astype(jnp.float32) * scale2
    if pad:
        out = out[:size]
    return out.reshape(orig_shape).astype(orig_dtype)


def _is_float(x):
    return jnp.issubdtype(getattr(x, "dtype", jnp.float32),
                          jnp.floating)


def reduce_sum(tree, axis="data", precision="f32"):
    """In-program all-reduce-sum of ``tree`` over the named mesh
    ``axis``. ``precision`` selects the wire tier (module docstring);
    ``f32`` IS ``lax.psum`` — bit-identical to the pre-existing pod
    gradient merge. Non-float leaves (error counts, confusion
    increments) always take the exact psum regardless of tier."""
    if precision not in REDUCE_PRECISIONS:
        raise ValueError("reduce precision must be one of %s, got %r"
                         % ("/".join(REDUCE_PRECISIONS), precision))
    if precision == "f32":
        return lax.psum(tree, axis)

    def leaf(x):
        if not _is_float(x):
            return lax.psum(x, axis)
        if precision == "bf16":
            return lax.psum(x.astype(jnp.bfloat16), axis) \
                .astype(x.dtype)
        return _int8_allreduce_leaf(x, axis)

    return jax.tree.map(leaf, tree)


def reduce_mean(tree, axis="data", precision="f32"):
    """In-program all-reduce-mean over ``axis`` (sum / static axis
    size)."""
    summed = reduce_sum(tree, axis=axis, precision=precision)
    n = None

    def leaf(x):
        nonlocal n
        if n is None:
            n = lax.axis_size(axis)
        return x / n if _is_float(x) else x // n

    return jax.tree.map(leaf, summed)


# -- wire-byte accounting -----------------------------------------------------

def reduce_wire_bytes(tree, n_devices, precision="f32"):
    """Analytic bytes-on-the-wire (total across all devices, the
    reshard.py convention) of one :func:`reduce_sum` of ``tree`` over
    ``n_devices`` shards. Zero when nothing crosses the wire (n=1)."""
    n = int(n_devices)
    if n <= 1:
        return 0
    total = 0
    for leaf in jax.tree.leaves(tree):
        size = 1
        for dim in getattr(leaf, "shape", ()):
            size *= int(dim)
        dtype = numpy.dtype(getattr(leaf, "dtype", numpy.float32))
        itemsize = dtype.itemsize
        is_float = numpy.issubdtype(dtype, numpy.floating)
        if precision == "f32" or not is_float:
            total += 2 * (n - 1) * size * itemsize
        elif precision == "bf16":
            total += 2 * (n - 1) * size * 2
        else:  # int8: a2a + all_gather int8 payloads + 2 scalar pmaxes
            padded = size + ((-size) % n)
            total += 2 * (n - 1) * padded + 2 * 2 * (n - 1) * 4
    return total


# -- runtime stats (the /metrics plane) ---------------------------------------

class ReduceStats:
    """Per-precision in-program-reduce bookkeeping: steps, wire bytes,
    and the host-cadence idle fraction — the share of fleet-training
    wall time the driver spends OUTSIDE the compiled step (frames,
    protocol, bookkeeping). Host-aggregated training idles ~everything;
    the in-program path pushes this toward zero (the observable the
    compiler-visible refit exists to move). Thread-safe; fed by the
    :func:`fleet_train_step` wrappers."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tiers = {}          # precision -> {"steps", "bytes"}
        self._busy = 0.0          # seconds inside the compiled step
        self._span_start = None   # cadence window start (monotonic)
        self._last_end = None

    def note(self, precision, wire_bytes=0, busy=0.0, now=None):
        now = time.monotonic() if now is None else now
        with self._lock:
            tier = self._tiers.setdefault(precision,
                                          {"steps": 0, "bytes": 0})
            tier["steps"] += 1
            tier["bytes"] += int(wire_bytes)
            if self._last_end is None \
                    or now - self._last_end > CADENCE_RESET:
                # a lull re-arms the window: idle between runs is not
                # protocol overhead
                self._span_start = now - busy
                self._busy = 0.0
            self._busy += float(busy)
            self._last_end = now

    def idle_fraction(self):
        with self._lock:
            if self._span_start is None or self._last_end is None:
                return None
            span = self._last_end - self._span_start
            if span <= 0 or self._busy <= 0:
                return None
            return min(max(1.0 - self._busy / span, 0.0), 1.0)

    def snapshot(self):
        with self._lock:
            return {precision: dict(entry)
                    for precision, entry in self._tiers.items()}

    def reset(self):
        with self._lock:
            self._tiers.clear()
            self._busy = 0.0
            self._span_start = None
            self._last_end = None


_stats = ReduceStats()


def get_reduce_stats():
    return _stats


def publish_reduce_stats(registry):
    """Scrape-time re-publication (the bridge contract) — wired into
    ``observe/xla_stats.publish_xla_stats`` so every ``/metrics`` mount
    (serving, web-status, the fleet master sidecar) and every fleet
    slave's piggybacked snapshot carries the reduce plane."""
    snap = _stats.snapshot()
    for precision, entry in snap.items():
        registry.counter_set(
            "veles_fleet_reduce_steps_total", entry["steps"],
            labels={"precision": precision},
            help="in-program data-parallel reduce steps executed")
        registry.counter_set(
            "veles_fleet_reduce_bytes_total", entry["bytes"],
            labels={"precision": precision},
            help="analytic collective wire bytes moved by in-program "
                 "gradient reduces (reshard.py convention: total "
                 "across devices)")
    idle = _stats.idle_fraction()
    if idle is not None:
        registry.set(
            "veles_fleet_chip_idle_fraction", round(idle, 4),
            help="share of fleet-training wall time spent outside the "
                 "compiled step (host protocol/frames) — the quantity "
                 "in-program aggregation exists to minimize")


# -- the fleet train step -----------------------------------------------------

#: id(build_tick steps) + precision -> wrapped step tuple
_WRAP_CACHE = {}


def _grad_bytes(params, n, precision):
    """Wire bytes of one train-step gradient reduce: the grad tree
    mirrors the per-layer ``"p"`` leaves."""
    grads = [entry.get("p", {}) for entry in params
             if isinstance(entry, dict)]
    return reduce_wire_bytes(grads, n, precision)


def _wrap_step(name, fn, precision, bytes_of, sync_for_stats=False):
    """Instrument one compiled step: compiles/FLOPs via
    ``xla_stats.instrument``, per-call wire bytes + busy/cadence into
    :class:`ReduceStats`, cadence into the MFU tracker and the
    ``veles_fleet_reduce_seconds`` histogram. Disabled-tracker calls
    pay one attribute check (the observability fast-path contract).

    ``sync_for_stats``: block on the step's METRIC outputs before
    stamping the busy window — jax dispatch is asynchronous, so the
    raw call wall is microseconds of enqueueing and would book a fully
    chip-bound run as ~100% idle. Enabled for the per-minibatch step
    programs (the fleet-slave path, where the metric scalars get
    host-read microseconds later anyway — the Decision payload — so
    the sync costs ~nothing); the SWEEP programs stay unsynced (the
    pipelined standalone engine hides that sync by design; they book
    steps/bytes only, never busy, so they cannot skew the gauge)."""
    from veles_tpu.observe.xla_stats import (get_compile_tracker,
                                             instrument)

    inst = instrument(name, fn)
    tracker = get_compile_tracker()
    state = {"last": None}

    def call(*args, **kwargs):
        if not tracker.enabled:
            return inst(*args, **kwargs)
        t0 = time.perf_counter()
        out = inst(*args, **kwargs)
        busy = 0.0
        if sync_for_stats:
            # metrics only — the params leaf stays in flight
            jax.block_until_ready(out[1] if isinstance(out, tuple)
                                  and len(out) == 2 else out)
            busy = time.perf_counter() - t0
        t1 = time.perf_counter()
        last = state["last"]
        state["last"] = t1
        _stats.note(precision, wire_bytes=bytes_of(args), busy=busy)
        if last is not None and t1 - last <= CADENCE_RESET:
            # cadence (time per step incl. host gaps) is the honest
            # step denominator for distributed MFU — the PR 5 serving
            # doctrine (collect_chunk cadence) applied to training
            cadence = t1 - last
            tracker.observe_step(name, cadence)
            from veles_tpu.observe.metrics import get_metrics_registry
            get_metrics_registry().observe(
                "veles_fleet_reduce_seconds", cadence,
                labels={"program": name, "precision": precision},
                help="wall seconds per in-program-reduced fleet step "
                     "(the reduce is fused into the step program)")
        return out

    call.program_name = name
    call.__wrapped__ = fn
    return call


def fleet_train_step(mesh, specs, norm_type="none", with_confusion=True,
                     augment="none", loss_kind="softmax",
                     reduce_precision=None):
    """The in-program data-parallel fleet step (ROADMAP item 3): the
    existing fused train step (``parallel/fused.py``) run per-shard of
    ``mesh``'s ``"data"`` axis with gradients merged by an in-program
    :func:`reduce_sum` at ``reduce_precision`` (default: the configured
    ``root.common.fleet.reduce`` tier) — ONE compiled program, zero
    host round trips per step, instrumented under ``observe/xla_stats``
    (programs ``mapreduce.fleet_{train,eval}_{step,sweep}``).

    Returns the same ``(train_step, eval_step, train_sweep,
    eval_sweep)`` tuple as ``fused.build_tick``; ``f32`` results are
    bit-identical to the raw ``build_tick(mesh=...)`` programs (the
    tick itself routes its psums through :func:`reduce_sum`)."""
    from veles_tpu.parallel import fused

    precision = reduce_precision_of(reduce_precision)
    steps = fused.build_tick(specs, norm_type, mesh=mesh,
                             with_confusion=with_confusion,
                             augment=augment, loss_kind=loss_kind,
                             grad_reduce=precision)
    key = (id(steps), precision)
    cached = _WRAP_CACHE.get(key)
    if cached is not None:
        return cached
    n = int(mesh.shape.get("data", 1)) if mesh is not None else 1
    train_step, eval_step, train_sweep, eval_sweep = steps

    def train_bytes(args):
        return _grad_bytes(args[0], n, precision)

    def sweep_bytes(args):
        rows = int(getattr(args[5], "shape", (1,))[0])
        return rows * _grad_bytes(args[0], n, precision)

    # eval reduces scalars (+ the confusion increment) — book the
    # scalar pair; the tier never compresses ints anyway
    scalar_wire = reduce_wire_bytes(
        (numpy.zeros((), numpy.float32), numpy.zeros((), numpy.int32)),
        n, "f32")

    def metric_bytes(args):
        return scalar_wire

    wrapped = (
        _wrap_step("mapreduce.fleet_train_step", train_step, precision,
                   train_bytes, sync_for_stats=True),
        _wrap_step("mapreduce.fleet_eval_step", eval_step, precision,
                   metric_bytes, sync_for_stats=True),
        _wrap_step("mapreduce.fleet_train_sweep", train_sweep,
                   precision, sweep_bytes),
        _wrap_step("mapreduce.fleet_eval_sweep", eval_sweep, precision,
                   metric_bytes),
    )
    _WRAP_CACHE[key] = wrapped
    return wrapped
