"""AOT bundle loader: deserialize + compile, gate, dispatch — zero retrace.

The consume half of the libVeles analogue (docs/aot_artifacts.md):
:func:`load_bundle` reads a sha-addressed bundle (``artifact.py``),
**strictly gates** it against this process — schema version, jax/jaxlib
versions, device fingerprint, mesh axes; any mismatch raises
:class:`AotCompatError` naming the stale field, and serving boot falls
back to live compilation (never a wrong-answer execute) — then
deserializes every StableHLO member and compiles it ONCE, eagerly, at
load. Cold start is deserialize + XLA-compile: no Python tracing, no
jaxpr, no shape-churned retraces.

:meth:`AotPrograms.bind` attaches the loaded programs to a
:class:`~veles_tpu.serving.ContinuousDecoder` after checking the
decoder's shape geometry field by field. The bound facade exposes the
SAME call signatures as the live jit surface (``decode.slot_admit_many``
et al.), dispatches per ``(program, shape key)``, converts the PRNG
``req_key`` wire format at the boundary (``decode.wire_slot_state`` —
a bit-level reinterpretation, so streams stay bit-identical), and books
every served call as a cache HIT under the program's existing
``observe/xla_stats`` name. The live ``veles_xla_compiles_total``
counters never move for AOT-served programs — the flat counter IS the
zero-retrace proof the acceptance tests pin. A shape the bundle does
not cover (e.g. the paged tail-admission family) falls back to the
live jit path and counts in ``veles_aot_misses_total``.
"""

import threading
import time
import weakref

from veles_tpu.aot.artifact import SCHEMA_VERSION, read_bundle


class AotCompatError(ValueError):
    """An artifact refused by the compatibility gate; ``field`` names
    exactly what is stale (schema / jax / jaxlib / fingerprint / mesh /
    a geometry key), so the operator knows what to rebuild."""

    def __init__(self, field, message):
        super().__init__(message)
        self.field = field


#: live AotPrograms instances, for the /metrics collector
_LOADED = weakref.WeakSet()
_LOADED_LOCK = threading.Lock()

#: process-lifetime tallies (hits/misses per program, load+compile
#: wall): the Prometheus counters publish from HERE, not from the live
#: bundles — a bundle GC'd after a reload must never make an exported
#: counter DECREASE (the un-monotone-counter failure mode the prefix
#: cache's book-at-commit hardening fixed)
_TOTALS = {"hits": {}, "misses": {}, "wall": 0.0}
_TOTALS_LOCK = threading.Lock()


def _tally(kind, name):
    with _TOTALS_LOCK:
        store = _TOTALS[kind]
        store[name] = store.get(name, 0) + 1


def _tally_wall(seconds):
    with _TOTALS_LOCK:
        _TOTALS["wall"] += float(seconds)


def _stop_all_prefetchers():
    """Interpreter-exit hook: ask every loaded bundle's prefetch
    workers to stop after their current compile. The workers are
    non-daemon on purpose — killing a thread inside an XLA compile
    aborts the process from C++ — so exit waits at most one compile."""
    with _LOADED_LOCK:
        loaded = list(_LOADED)
    for programs in loaded:
        programs.stop_prefetch()


# threading._register_atexit (the concurrent.futures hook) runs BEFORE
# threading._shutdown joins non-daemon threads; plain atexit runs
# after, which would make a short-lived process wait out the whole
# warm-up queue instead of just the in-flight compile
try:
    from threading import _register_atexit as _register_exit_hook
except ImportError:  # very old pythons: bounded by the queue instead
    from atexit import register as _register_exit_hook

_register_exit_hook(_stop_all_prefetchers)


def _current_fingerprint():
    from veles_tpu.observe.regress import device_fingerprint
    return device_fingerprint()


def check_compat(manifest, mesh=None):
    """The strict gate. Raises :class:`AotCompatError` naming the first
    stale field; returns None when the bundle may load here."""
    import jax
    import jaxlib

    schema = manifest.get("schema")
    if schema != SCHEMA_VERSION:
        raise AotCompatError(
            "schema", "bundle schema %r != supported %d — rebuild the "
            "artifact with this veles_tpu" % (schema, SCHEMA_VERSION))
    for field, current in (("jax", jax.__version__),
                           ("jaxlib", jaxlib.__version__)):
        recorded = manifest.get(field)
        if recorded != current:
            raise AotCompatError(
                field, "bundle was exported under %s %s but this "
                "process runs %s — refusing stale compiled programs; "
                "rebuild with `veles_tpu aot build`"
                % (field, recorded, current))
    recorded = manifest.get("fingerprint") or {}
    current = _current_fingerprint()
    for key in ("backend", "device_kind", "device_count"):
        if recorded.get(key) != current.get(key):
            raise AotCompatError(
                "fingerprint", "bundle device fingerprint %s=%r does "
                "not match this machine's %r — compiled programs are "
                "device-specific; rebuild on matching hardware"
                % (key, recorded.get(key), current.get(key)))
    bundle_mesh = manifest.get("mesh")
    if bundle_mesh is None:
        if mesh is not None:
            raise AotCompatError(
                "mesh", "bundle holds single-chip programs but a mesh "
                "%r was requested — rebuild with --mesh"
                % dict(mesh.shape))
    else:
        if mesh is None:
            raise AotCompatError(
                "mesh", "bundle holds programs for mesh axes %r but no "
                "serving mesh was configured (--serve-mesh)"
                % bundle_mesh.get("axes"))
        if dict(bundle_mesh.get("axes") or {}) != dict(mesh.shape):
            raise AotCompatError(
                "mesh", "bundle mesh axes %r != serving mesh %r"
                % (bundle_mesh.get("axes"), dict(mesh.shape)))


def _compile_entry(row, blob, mesh):
    """Deserialize one StableHLO member and compile it: the only XLA
    work an AOT boot pays. Returns the executable."""
    import jax
    from jax import export as jax_export

    exported = jax_export.deserialize(bytearray(blob))
    if mesh is not None:
        shardings = exported.in_shardings_jax(mesh)
    else:
        shardings = (None,) * len(exported.in_avals)
    flat = []
    for aval, sharding in zip(exported.in_avals, shardings):
        try:
            flat.append(jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                             sharding=sharding))
        except (TypeError, ValueError):
            flat.append(jax.ShapeDtypeStruct(aval.shape, aval.dtype))
    args, kwargs = jax.tree.unflatten(exported.in_tree, flat)
    jitted = jax.jit(exported.call,
                     donate_argnums=tuple(row.get("donate") or ()))
    return jitted.lower(*args, **kwargs).compile()


class _Entry:
    """One loaded program: compiled on first use (or by the prefetch
    workers), consuming serialized StableHLO — no Python tracing ever
    happens again. Per-entry locking lets an on-demand dispatch
    compile ITS program concurrently with the background warm-up (XLA
    compilation releases the GIL), so first-token latency is one
    parallel compile, not a queue."""

    __slots__ = ("row", "blob", "blob_bytes", "mesh", "compiled",
                 "compile_seconds", "lock", "cache", "cache_key",
                 "from_cache")

    def __init__(self, row, blob, mesh, cache=None, cache_key=None):
        self.row = row
        self.blob = blob
        #: serialized program size, recorded before get() clears the
        #: blob — the footprint proxy memscope's ``aot_executables``
        #: accountant sums (the compiled executable's device size is
        #: not introspectable, and the StableHLO bytes track it)
        self.blob_bytes = len(blob) if blob is not None else 0
        self.mesh = mesh
        self.compiled = None
        self.compile_seconds = 0.0
        self.lock = threading.Lock()
        #: persistent executable cache (aot/exec_cache.py) + this
        #: program's content address in it; None = cache disabled
        self.cache = cache
        self.cache_key = cache_key
        #: True when the executable was deserialized from the
        #: persistent cache instead of XLA-compiled (the bench's
        #: load-time-compiles-pinned-zero proof reads this)
        self.from_cache = False

    def get(self):
        if self.compiled is not None:
            return self.compiled
        with self.lock:
            if self.compiled is None:
                t0 = time.perf_counter()
                compiled = None
                if self.cache is not None \
                        and self.cache_key is not None:
                    compiled = self.cache.load(self.cache_key)
                    if compiled is not None:
                        self.from_cache = True
                if compiled is None:
                    compiled = _compile_entry(self.row, self.blob,
                                              self.mesh)
                    if self.cache is not None \
                            and self.cache_key is not None:
                        self.cache.store(self.cache_key, compiled)
                self.compile_seconds = time.perf_counter() - t0
                _tally_wall(self.compile_seconds)
                self.blob = None  # the executable replaces the bytes
                self.compiled = compiled
        return self.compiled


class AotPrograms:
    """A loaded bundle: compiled programs keyed by (name, shape key),
    dispatch stats, and the decoder-binding facade."""

    def __init__(self, manifest, entries, path=None,
                 load_seconds=0.0, exec_cache=None):
        self.manifest = manifest
        self.path = path
        self.geometry = manifest.get("geometry")
        self.chunk = manifest.get("chunk")
        self._entries = entries         # (name, key tuple) -> _Entry
        self.load_seconds = load_seconds
        #: persistent executable cache in use, or None (exec_cache.py)
        self.exec_cache = exec_cache
        self._lock = threading.Lock()
        self._prefetchers = []
        self._prefetch_stop = threading.Event()
        self.hits = {}
        self.misses = {}
        with _LOADED_LOCK:
            _LOADED.add(self)
        # per-owner HBM attribution (observe/memscope.py): every live
        # bundle reports its footprint under "aot_executables"; the
        # weakref registry drops this bundle when it is collected
        try:
            from veles_tpu.observe.memscope import get_memscope
            get_memscope().register(
                "aot_executables", self,
                lambda programs: programs.footprint_bytes())
        except Exception:
            pass

    def __len__(self):
        return len(self._entries)

    def footprint_bytes(self):
        """Loaded-program footprint: the serialized StableHLO bytes of
        every entry (recorded at load — the compiled executable's
        device size is not introspectable, and the blob size tracks
        it). Lock-free: ``_entries`` is write-once at load time."""
        return sum(entry.blob_bytes
                   for entry in self._entries.values())

    def _prefetch_order(self):
        """Step/dispatch programs first (every request needs one),
        then admits smallest-group-first (a lone cold request admits
        as group 1) — the order a fresh replica's first traffic
        actually wants its programs in."""
        def rank(item):
            (name, key), _ = item
            family = 0 if ("step" in name or "dispatch" in name) else 1
            return (family, key[-1] if len(key) > 1 else 0, key)
        return [entry for _, entry in sorted(self._entries.items(),
                                             key=rank)]

    def prefetch(self, workers=None):
        """Warm every program on background threads. XLA compilation
        releases the GIL, so the warm-up overlaps the decoder build
        and the first requests; an on-demand dispatch never queues —
        per-entry locks let it compile its own program concurrently."""
        import os

        if workers is None:
            workers = max(1, min(4, os.cpu_count() or 1))
        queue = self._prefetch_order()
        index = {"next": 0}
        index_lock = threading.Lock()

        def worker():
            while not self._prefetch_stop.is_set():
                with index_lock:
                    i = index["next"]
                    index["next"] = i + 1
                if i >= len(queue):
                    return
                try:
                    queue[i].get()
                except Exception:
                    import logging
                    logging.getLogger("aot").exception(
                        "prefetch compile failed for %s",
                        queue[i].row.get("name"))

        # NON-daemon: a thread killed inside an XLA compile aborts the
        # whole process from C++; the atexit hook stops the workers
        # after their current entry instead
        self._prefetchers = [
            threading.Thread(target=worker, name="aot-prefetch-%d" % i)
            for i in range(workers)]
        for thread in self._prefetchers:
            thread.start()
        return self

    def stop_prefetch(self):
        """Stop the background warm-up after the in-flight compiles
        (on-demand ``program()`` calls still compile lazily)."""
        self._prefetch_stop.set()
        for thread in self._prefetchers:
            if thread.is_alive():
                thread.join()
        self._prefetchers = []

    def compile_all(self):
        """Compile every program now, blocking (the pre-warmed boot:
        fixed load cost, zero first-dispatch stalls afterwards)."""
        t0 = time.perf_counter()
        for entry in self._entries.values():
            entry.get()
        for thread in self._prefetchers:
            thread.join()
        self.load_seconds += time.perf_counter() - t0
        return self

    def program(self, name, key):
        """The compiled executable for ``(name, key)`` or None — the
        generic access path (the fused tick loader uses it; the
        serving facade goes through :meth:`bind`). Compiles lazily on
        first use; the compile consumes serialized StableHLO, never a
        Python trace."""
        entry = self._entries.get((name, tuple(key)))
        if entry is None:
            return None
        return entry.get()

    def keys(self):
        return sorted(self._entries)

    def prewarm_bucket(self, bucket):
        """Compile every not-yet-compiled admit-family program whose
        shape key names ``bucket`` (the serving governor's hot-bucket
        actuator — docs/serving_robustness.md). Blocking; callers run
        it on a background thread so the first cold admission of a
        trending bucket finds its program already executable. Returns
        the number of programs compiled."""
        warmed = 0
        for (name, key), entry in sorted(self._entries.items()):
            # admit shape keys put the prompt bucket at key[1] (dense
            # ("admit", bucket, group), paged ("paged_admit", bucket,
            # group, pb)) — positional match, NOT membership, so a
            # bucket equal to another entry's group-size element never
            # prewarms unrelated programs
            if "admit" not in name or len(key) < 2 or key[1] != bucket:
                continue
            if entry.compiled is None:
                entry.get()
                warmed += 1
        return warmed

    # -- bookkeeping ------------------------------------------------------
    def _book_hit(self, name):
        from veles_tpu.observe.xla_stats import get_compile_tracker

        with self._lock:
            self.hits[name] = self.hits.get(name, 0) + 1
        _tally("hits", name)
        tracker = get_compile_tracker()
        if tracker.enabled:
            # the loaded program serves under its existing xla_stats
            # name as a cache HIT — compiles stay flat, which is the
            # device-truth zero-retrace proof
            tracker.record_hit(name)

    def _book_miss(self, name):
        with self._lock:
            self.misses[name] = self.misses.get(name, 0) + 1
        _tally("misses", name)

    def stats(self):
        compiled = sum(1 for e in self._entries.values()
                       if e.compiled is not None)
        from_cache = sum(1 for e in self._entries.values()
                         if e.from_cache)
        compile_seconds = sum(e.compile_seconds
                              for e in self._entries.values())
        with self._lock:
            out = {"programs": len(self._entries),
                   "compiled": compiled,
                   # executables deserialized from the persistent
                   # cache vs XLA-compiled live this process — the
                   # cached-boot "load-time compiles pinned 0" proof
                   "from_cache": from_cache,
                   "compiled_live": compiled - from_cache,
                   "compile_seconds": round(compile_seconds, 4),
                   "load_seconds": round(self.load_seconds, 4),
                   "hits": dict(self.hits),
                   "misses": dict(self.misses)}
        if self.exec_cache is not None:
            out["exec_cache"] = self.exec_cache.stats()
        return out

    # -- serving facade ---------------------------------------------------
    def bind(self, decoder):
        """Validate ``decoder``'s shape geometry against the bundle's
        and return the bound call facade. Raises
        :class:`AotCompatError` naming the first mismatching geometry
        field — the caller (``ContinuousDecoder``) degrades to live
        compilation with a loud warning, never a wrong execute."""
        from veles_tpu.aot.artifact import decoder_geometry

        if self.geometry is None:
            raise AotCompatError(
                "geometry", "bundle %r holds no serving geometry (not "
                "a serving bundle)" % (self.path,))
        live = decoder_geometry(decoder)
        for field in sorted(set(self.geometry) | set(live)):
            if self.geometry.get(field) != live.get(field):
                raise AotCompatError(
                    field, "bundle geometry %s=%r does not match the "
                    "serving configuration's %r — rebuild the artifact "
                    "or align the serving flags"
                    % (field, self.geometry.get(field),
                       live.get(field)))
        return _BoundAot(self, decoder)


class _BoundAot:
    """Per-decoder dispatch facade: the live jit surface's signatures,
    backed by the loaded executables, falling back to the decoder's own
    live resolution (sharded fns or late module binding — the chaos
    seam keeps working) on any uncovered shape."""

    def __init__(self, programs, decoder):
        self._programs = programs
        self._decoder = weakref.ref(decoder)
        #: (program_name, served_from_aot) of the most recent dispatch
        #: through this facade — the request ledger's per-dispatch
        #: aot/live attribution seam (read by the decoder right after
        #: the call returns, single driver thread)
        self.last_dispatch = None
        self._fits = {}     # (name, key) -> avals matched this decoder

    # live fallback resolvers (the decoder's own late-binding rules)
    def _live_dense(self, module_name):
        from veles_tpu.parallel import decode

        return getattr(decode, module_name)

    def _live_paged(self, index, module_name):
        from veles_tpu.parallel import kv_pool

        dec = self._decoder()
        if dec is not None and dec._paged_fns:
            return dec._paged_fns[index]
        return getattr(kv_pool, module_name)

    def _call(self, name, key, wire_args, state_only, fallback):
        """One dispatch: lookup -> wire-convert -> execute -> unwire,
        or fall back to the live jit surface."""
        from veles_tpu.parallel.decode import unwire_slot_state

        entry = self._programs._entries.get((name, tuple(key)))
        # a program whose recorded avals are not this call's (a bundle
        # built when the slab was one stacked array) is a miss, not an
        # execute into donated buffers of another shape. A decoder's
        # operands keep their shapes: one verdict per program
        fits = self._fits.get((name, tuple(key)))
        if fits is None and entry is not None:
            fits = self._fits[name, tuple(key)] = _avals_match(
                entry.row, wire_args)
        compiled = entry.get() if fits else None
        if compiled is None:
            self._programs._book_miss(name)
            self.last_dispatch = (name, False)
            return fallback()
        self._programs._book_hit(name)
        self.last_dispatch = (name, True)
        out = compiled(*wire_args)
        if state_only:
            return unwire_slot_state(out)
        state, emitted = out
        return unwire_slot_state(state), emitted

    # -- dense ------------------------------------------------------------
    def admit(self, params, embed_table, heads, state, slots, x,
              req_keys, lengths):
        import jax
        from veles_tpu.parallel.decode import wire_slot_state

        key = ("admit", int(x.shape[1]), int(x.shape[0]))
        return self._call(
            "decode.admit", key,
            (params, embed_table, wire_slot_state(state), slots, x,
             jax.random.key_data(req_keys), lengths), True,
            lambda: self._live_dense("slot_admit_many")(
                params, embed_table, heads, state, slots, x, req_keys,
                lengths))

    def step(self, params, embed_table, heads, state, active,
             temperature=1.0, sample=False, top_k=0, span=None):
        from veles_tpu.parallel.decode import wire_slot_state

        key = ("step", int(span))
        return self._call(
            "decode.step", key,
            (params, embed_table, wire_slot_state(state), active,
             temperature), False,
            lambda: self._live_dense("slot_step")(
                params, embed_table, heads, state, active, temperature,
                sample=sample, top_k=top_k, span=span))

    def step_many(self, params, embed_table, heads, state, active, n,
                  temperature=1.0, sample=False, top_k=0, span=None):
        from veles_tpu.parallel.decode import wire_slot_state

        key = ("dispatch", int(n), int(span))
        return self._call(
            "decode.dispatch", key,
            (params, embed_table, wire_slot_state(state), active,
             temperature), False,
            lambda: self._live_dense("slot_step_many")(
                params, embed_table, heads, state, active, n,
                temperature, sample=sample, top_k=top_k, span=span))

    # -- paged ------------------------------------------------------------
    def paged_admit(self, params, embed_table, heads, state, slots,
                    page_ids, x, req_keys, lengths):
        import jax
        from veles_tpu.parallel.decode import wire_slot_state

        key = ("paged_admit", int(x.shape[1]), int(x.shape[0]),
               int(page_ids.shape[1]))
        return self._call(
            "paged.admit", key,
            (params, embed_table, wire_slot_state(state), slots,
             page_ids, x, jax.random.key_data(req_keys), lengths),
            True,
            lambda: self._live_paged(0, "paged_admit_many")(
                params, embed_table, heads, state, slots, page_ids, x,
                req_keys, lengths))

    def paged_admit_tail(self, params, embed_table, heads, state,
                         slots, prefix_pages, tail_pages, tail_x,
                         req_keys, lengths):
        """The tail family's key space (cached-prefix page count x
        tail bucket) is unbounded at build time — always the live
        path, counted as a miss so the fallback is observable."""
        self._programs._book_miss("paged.admit_tail")
        self.last_dispatch = ("paged.admit_tail", False)
        return self._live_paged(1, "paged_admit_tail")(
            params, embed_table, heads, state, slots, prefix_pages,
            tail_pages, tail_x, req_keys, lengths)

    def paged_admit_hit(self, state, slots, lengths, logits, req_keys):
        import jax
        from veles_tpu.parallel.decode import wire_slot_state

        key = ("paged_hit", int(slots.shape[0]))
        return self._call(
            "paged.admit_hit", key,
            (wire_slot_state(state), slots, lengths, logits,
             jax.random.key_data(req_keys)), True,
            lambda: self._live_paged(2, "paged_admit_hit")(
                state, slots, lengths, logits, req_keys))

    def paged_step(self, params, embed_table, heads, state, page_table,
                   active, temperature=1.0, sample=False, top_k=0):
        from veles_tpu.parallel.decode import wire_slot_state

        key = ("paged_step", int(page_table.shape[1]))
        return self._call(
            "paged.step", key,
            (params, embed_table, wire_slot_state(state), page_table,
             active, temperature), False,
            lambda: self._live_paged(3, "paged_slot_step")(
                params, embed_table, heads, state, page_table, active,
                temperature, sample=sample, top_k=top_k))

    def paged_step_many(self, params, embed_table, heads, state,
                        page_table, active, n, temperature=1.0,
                        sample=False, top_k=0):
        from veles_tpu.parallel.decode import wire_slot_state

        key = ("paged_dispatch", int(n), int(page_table.shape[1]))
        return self._call(
            "paged.dispatch", key,
            (params, embed_table, wire_slot_state(state), page_table,
             active, temperature), False,
            lambda: self._live_paged(4, "paged_slot_step_many")(
                params, embed_table, heads, state, page_table, active,
                n, temperature, sample=sample, top_k=top_k))


def _avals_match(row, args):
    """True when a call's operand shapes/dtypes equal the exported
    program's recorded avals — the upfront check that keeps a
    mismatched call on the live path instead of a donated-buffer
    explosion inside the executable."""
    import jax

    want = row.get("in_avals") or []
    leaves = [leaf for leaf in jax.tree.leaves(args)
              if hasattr(leaf, "shape")]
    if len(want) != len(leaves):
        return False
    for row, leaf in zip(want, leaves):
        if list(leaf.shape) != list(row[0]) \
                or str(leaf.dtype) != row[1]:
            return False
    return True


def _tick_dispatch(programs, name, key_head, live_fn, mb_arg):
    """A fused-tick step that serves matching-shape calls from the
    bundle and falls back to the (lazily-compiled) live jit."""
    def dispatch(*args):
        mb = int(args[mb_arg].shape[0])
        entry = programs._entries.get((name, (key_head, mb)))
        if entry is None or not _avals_match(entry.row, args):
            programs._book_miss(name)
            return live_fn(*args)
        programs._book_hit(name)
        return programs.program(name, (key_head, mb))(*args)

    dispatch.__wrapped__ = live_fn
    return dispatch


def install_fused_tick(programs, specs, norm_type="none", mesh=None,
                       with_confusion=True, augment="none",
                       loss_kind="softmax", grad_reduce="f32"):
    """Slot a bundle's fused-tick programs into ``parallel/fused``'s
    tick cache (``install_tick_steps``): any later ``build_tick`` /
    ``FusedTick`` with this topology runs the LOADED train/eval step
    for matching minibatch shapes and the live jit for everything else
    (sweeps, odd tail minibatches). ``jax.jit`` is lazy, so the live
    fallbacks cost nothing until an uncovered shape actually runs —
    the covered steady-state path never traces. Returns the installed
    step tuple."""
    from veles_tpu.parallel import fused

    live = fused.build_tick(specs, norm_type, mesh=mesh,
                            with_confusion=with_confusion,
                            augment=augment, loss_kind=loss_kind,
                            grad_reduce=grad_reduce)
    steps = (_tick_dispatch(programs, "fused.train_step", "train_step",
                            live[0], mb_arg=5),
             _tick_dispatch(programs, "fused.eval_step", "eval_step",
                            live[1], mb_arg=4),
             live[2], live[3])
    fused.install_tick_steps(steps, specs, norm_type=norm_type,
                             mesh=mesh, with_confusion=with_confusion,
                             augment=augment, loss_kind=loss_kind,
                             grad_reduce=grad_reduce)
    return steps


def load_bundle(path, mesh=None, eager=False, prefetch=True,
                exec_cache=None):
    """Read, gate and load a bundle. Returns :class:`AotPrograms`.
    Raises :class:`AotCompatError` (stale bundle, named field) or
    ``ValueError`` (tampered/torn bundle) — in both cases nothing
    half-loaded escapes.

    By default the programs compile on background prefetch threads
    (first-traffic order) AND on demand at first dispatch — XLA
    compilation releases the GIL, so the warm-up overlaps the decoder
    build and cold-start-to-first-token pays ONE parallel compile.
    ``eager=True`` instead blocks until everything is compiled (the
    pre-warmed replica); ``prefetch=False`` disables the background
    threads (deterministic tests). Every path compiles from serialized
    StableHLO — zero Python tracing in all cases.

    ``exec_cache`` enables the persistent executable cache
    (``aot/exec_cache.py``): ``True`` = the conventional
    ``<bundle>.xcache`` sibling directory, a string = that directory,
    ``False`` = off, ``None`` (default) = resolve from
    ``root.common.serve.aot_cache``. With a warm cache a matching
    machine deserializes executables instead of XLA-compiling them —
    ``coldstart_cached_to_first_token_ms`` approaches pure weight
    load. A torn or mismatching entry is refused loudly and that
    program compiles live (docs/zero_downtime.md)."""
    from veles_tpu.aot.exec_cache import (cache_fingerprint, entry_key,
                                          resolve_cache)

    t0 = time.perf_counter()
    manifest, members = read_bundle(path)
    check_compat(manifest, mesh=mesh)
    cache = resolve_cache(exec_cache, path)
    fingerprint = cache_fingerprint(mesh) if cache is not None else None
    entries = {}
    for row in manifest.get("programs", ()):
        entries[(row["name"], tuple(row["key"]))] = _Entry(
            row, members[row["member"]], mesh, cache=cache,
            cache_key=(entry_key(row, fingerprint)
                       if cache is not None else None))
    load_seconds = time.perf_counter() - t0
    _tally_wall(load_seconds)
    programs = AotPrograms(manifest, entries, path=path,
                           load_seconds=load_seconds,
                           exec_cache=cache)
    if eager:
        programs.compile_all()
    elif prefetch:
        programs.prefetch()
    return programs


def publish_aot_stats(registry):
    """Scrape-time collector (wired through ``observe/xla_stats``'s
    device-truth collector): loaded-program counts, load wall, and the
    hit/miss tallies whose flat-compile twin proves zero retrace."""
    with _LOADED_LOCK:
        loaded = list(_LOADED)
    with _TOTALS_LOCK:
        hits = dict(_TOTALS["hits"])
        misses = dict(_TOTALS["misses"])
        wall = _TOTALS["wall"]
    if not loaded and not hits and not misses and not wall:
        return
    # the GAUGE aggregates over LIVE bundles (may shrink after a
    # reload); the COUNTERS publish from the process-lifetime tallies
    # so a GC'd bundle can never make them decrease (monotone by
    # construction — a drop would read as a counter reset and produce
    # bogus rate() spikes)
    registry.set("veles_aot_programs_loaded",
                 sum(len(programs) for programs in loaded),
                 help="compiled programs held by live AOT bundles")
    registry.counter_set(
        "veles_aot_load_seconds_total", round(wall, 6),
        help="wall seconds spent loading + compiling AOT bundles")
    for name, count in hits.items():
        registry.counter_set(
            "veles_aot_hits_total", count,
            labels={"program": name},
            help="dispatches served by AOT-loaded programs")
    for name, count in misses.items():
        registry.counter_set(
            "veles_aot_misses_total", count,
            labels={"program": name},
            help="dispatches that fell back to live compilation")
    from veles_tpu.aot.exec_cache import totals as xc_totals
    xc = xc_totals()
    if any(xc.values()):
        registry.counter_set(
            "veles_aot_exec_cache_hits_total", xc["hits"],
            help="executables deserialized from the persistent "
                 "executable cache instead of XLA-compiled")
        registry.counter_set(
            "veles_aot_exec_cache_misses_total", xc["misses"],
            help="persistent-executable-cache lookups that fell "
                 "back to live XLA compilation")
        registry.counter_set(
            "veles_aot_exec_cache_writes_total", xc["writes"],
            help="executables serialized into the persistent "
                 "executable cache")
        registry.counter_set(
            "veles_aot_exec_cache_rejects_total", xc["rejects"],
            help="torn/tampered persistent-cache entries refused "
                 "by the sha256 sidecar check")
