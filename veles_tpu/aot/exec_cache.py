"""Persistent executable cache: skip load-time XLA compiles entirely.

An AOT bundle (``artifact.py`` / ``loader.py``) removes Python tracing
from cold start, but a fresh process still pays one XLA compile per
program — the dominant residue of ``coldstart_to_first_token_ms``
(docs/zero_downtime.md records the measured numbers). This module adds
the missing half of the libVeles "ship the runnable thing" doctrine: a
content-addressed, fingerprint-gated, on-disk cache of the *compiled*
executables (``jax.experimental.serialize_executable``), kept beside
the bundle. A matching machine deserializes instead of compiling, so a
warm boot approaches pure weight-load time.

Gating doctrine (same as :func:`~veles_tpu.aot.loader.check_compat`,
applied per entry): the cache key digests the program's StableHLO
member hash together with the jax/jaxlib versions, the device
fingerprint (backend / device kind / device count), the mesh axes and
the donation tuple — ANY environment drift changes every key, so a
stale executable is simply never found (a miss, never a wrong execute).

Torn/partial-write robustness (the snapshotter's idiom, satellite of
docs/zero_downtime.md): each entry lands via temp + ``os.replace``
with a ``.sha256`` sidecar hashed on the write path, the sidecar
renamed FIRST; a truncated or bit-flipped entry fails the sidecar
check and the loader falls back to live compilation with ONE loud
warning per entry (``veles_aot_exec_cache_rejects_total`` counts it).

Note the serialized payload is a pickle (that is what
``serialize_executable`` produces): the sidecar defends against torn
writes and bit rot, not against an adversary who can already write to
the cache directory — treat the cache dir with the same trust as the
bundle itself.
"""

import hashlib
import json
import logging
import os
import pickle
import threading

logger = logging.getLogger("aot.ExecCache")

#: bump when the entry payload layout changes (part of every key)
CACHE_SCHEMA = 2

#: entry filename suffix (content-addressed: ``<key>.xc``)
ENTRY_SUFFIX = ".xc"

#: warn-once memory: one loud line per (cache, entry, reason) — a
#: thousand-program bundle with a torn cache must not scream a
#: thousand times
_WARNED = set()
_WARNED_LOCK = threading.Lock()

#: process-lifetime tallies (the Prometheus counters publish from
#: HERE, not from live caches — a cache GC'd with its bundle must
#: never make an exported counter decrease; same doctrine as the
#: loader's ``_TOTALS``)
_XC_TOTALS = {"hits": 0, "misses": 0, "writes": 0, "rejects": 0}
_XC_LOCK = threading.Lock()


def totals():
    """Snapshot of the process-lifetime hit/miss/write/reject tallies
    (monotone by construction — ``publish_aot_stats`` exports them)."""
    with _XC_LOCK:
        return dict(_XC_TOTALS)


def _warn_once(key, message, *args):
    with _WARNED_LOCK:
        if key in _WARNED:
            return
        _WARNED.add(key)
    logger.warning(message, *args)


def cache_fingerprint(mesh=None):
    """The environment half of every entry key: compiled executables
    are specific to the XLA version AND the device topology, so all of
    it participates in the content address (drift = miss, never a
    wrong execute)."""
    import jax
    import jaxlib

    from veles_tpu.observe.regress import device_fingerprint

    fp = device_fingerprint()
    return {
        "schema": CACHE_SCHEMA,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": fp.get("backend"),
        "device_kind": fp.get("device_kind"),
        "device_count": fp.get("device_count"),
        "mesh": dict(mesh.shape) if mesh is not None else None,
    }


def entry_key(row, fingerprint):
    """Content address for one program: the bundle row's member sha
    (the StableHLO bytes), its donation tuple, and the environment
    fingerprint, digested canonically."""
    doc = {"name": row.get("name"),
           "key": list(row.get("key") or ()),
           "member": row.get("sha256"),
           "donate": list(row.get("donate") or ()),
           "env": fingerprint}
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()


def _sha256_of(path):
    digest = hashlib.sha256()
    with open(path, "rb") as fin:
        for block in iter(lambda: fin.read(1 << 20), b""):
            digest.update(block)
    return digest.hexdigest()


class _HashingWriter:
    """File-object tee feeding SHA-256 with every written block, so
    the sidecar digest costs no second full-file read (the
    snapshotter's exact idiom)."""

    def __init__(self, fileobj):
        self._file = fileobj
        self._digest = hashlib.sha256()

    def write(self, data):
        self._digest.update(data)
        return self._file.write(data)

    def flush(self):
        self._file.flush()

    def hexdigest(self):
        return self._digest.hexdigest()


class ExecutableCache:
    """One on-disk cache directory (conventionally
    ``<bundle>.xcache/``). Thread-safe for the loader's concurrent
    prefetch workers: load is read-only, store writes unique temp
    names and renames atomically behind a write lock (two workers
    storing the SAME key must not interleave their sidecar/entry
    renames — the cross of A's entry with B's sidecar would read as
    a torn entry), and the counters sit behind one small lock."""

    def __init__(self, directory):
        self.directory = directory
        self._lock = threading.Lock()
        self._write_lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.writes = 0
        #: sidecar-mismatch / unreadable-entry fallbacks (each also a
        #: miss — the caller compiled live)
        self.rejects = 0

    def _count(self, field):
        with self._lock:
            setattr(self, field, getattr(self, field) + 1)
        with _XC_LOCK:
            _XC_TOTALS[field] += 1

    def _path(self, key):
        return os.path.join(self.directory, key + ENTRY_SUFFIX)

    # -- read path --------------------------------------------------------
    def load(self, key):
        """The deserialized executable for ``key``, or None (miss /
        torn entry — the caller falls back to live compilation). A
        torn or tampered entry warns ONCE and is unlinked so the next
        live compile repairs it. The executable is loaded onto the
        devices it was compiled for (their ids ride beside the
        payload): left to its default, ``deserialize_and_load`` takes
        EVERY local device, and a one-device program then refuses its
        arguments on a host with more. An entry naming a device this
        process lacks is a plain miss."""
        import jax
        from jax.experimental.serialize_executable import \
            deserialize_and_load

        path = self._path(key)
        if not os.path.isfile(path):
            self._count("misses")
            return None
        sidecar = path + ".sha256"
        try:
            with open(sidecar, "r") as fin:
                want = [line.split()[0] for line in fin
                        if line.strip() and not line.startswith("#")]
            if not want or _sha256_of(path) not in want:
                raise ValueError(
                    "sha256 mismatch against sidecar %s" % sidecar)
            with open(path, "rb") as fin:
                payload, in_tree, out_tree, device_ids = pickle.load(fin)
            local = {device.id: device for device in jax.local_devices()}
            if not all(i in local for i in device_ids):
                self._count("misses")
                return None
            compiled = deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[local[i] for i in device_ids])
        except Exception as exc:
            # torn write, missing sidecar, bit rot, or a pickle from a
            # different jax than the key promised: refuse LOUDLY
            # (once) and fall back to live compilation — never execute
            # bytes the sidecar does not vouch for
            self._count("rejects")
            self._count("misses")
            _warn_once(
                ("reject", path),
                "executable cache entry %s refused (%s: %s) — falling "
                "back to live compilation; the entry will be rebuilt "
                "after the next compile", path, type(exc).__name__, exc)
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        self._count("hits")
        return compiled

    # -- write path -------------------------------------------------------
    def store(self, key, compiled):
        """Serialize ``compiled`` under ``key``: temp + ``os.replace``
        with the ``.sha256`` sidecar renamed FIRST (the snapshotter's
        crash-window discipline — whichever rename a crash interrupts,
        no reader ever sees unvouched bytes). Best-effort: a cache
        that cannot be written only costs the next boot a compile."""
        from jax.experimental.serialize_executable import serialize

        try:
            entry = serialize(compiled) + (
                [device.id for device in
                 compiled.runtime_executable().local_devices()],)
        except Exception as exc:
            _warn_once(
                ("serialize", self.directory, type(exc).__name__),
                "executable not serializable for the persistent cache "
                "(%s: %s) — boots will keep compiling live",
                type(exc).__name__, exc)
            return False
        path = self._path(key)
        name = os.path.basename(path)
        tmp = "%s.tmp%d.%d" % (path, os.getpid(),
                               threading.get_ident())
        try:
            os.makedirs(self.directory, exist_ok=True)
            with open(tmp, "wb") as raw:
                tee = _HashingWriter(raw)
                pickle.dump(entry, tee,
                            protocol=pickle.HIGHEST_PROTOCOL)
            sidecar_tmp = tmp + ".sha256"
            with open(sidecar_tmp, "w") as fout:
                fout.write("%s  %s\n" % (tee.hexdigest(), name))
            with self._write_lock:
                os.replace(sidecar_tmp, path + ".sha256")
                os.replace(tmp, path)
        except OSError as exc:
            _warn_once(
                ("store", self.directory),
                "persistent executable cache %s not writable (%s) — "
                "boots will keep compiling live", self.directory, exc)
            for leftover in (tmp, tmp + ".sha256"):
                try:
                    os.remove(leftover)
                except OSError:
                    pass
            return False
        self._count("writes")
        return True

    def stats(self):
        with self._lock:
            return {"directory": self.directory, "hits": self.hits,
                    "misses": self.misses, "writes": self.writes,
                    "rejects": self.rejects}


def resolve_cache(exec_cache, bundle_path):
    """Resolve a ``load_bundle(exec_cache=...)`` argument:

    - ``None``: read ``root.common.serve.aot_cache`` — truthy string =
      that directory, bare truthy = the conventional sibling dir,
      absent/falsy = disabled;
    - ``False``: disabled;
    - ``True``: the conventional ``<bundle>.xcache`` sibling;
    - a string: that directory;
    - an :class:`ExecutableCache`: used as-is.
    """
    if exec_cache is None:
        from veles_tpu.core.config import root
        exec_cache = root.common.serve.get("aot_cache", None)
        if not exec_cache:
            return None
    if exec_cache is False:
        return None
    if isinstance(exec_cache, ExecutableCache):
        return exec_cache
    if exec_cache is True or not isinstance(exec_cache, str):
        if bundle_path is None:
            return None
        exec_cache = str(bundle_path) + ".xcache"
    return ExecutableCache(exec_cache)
