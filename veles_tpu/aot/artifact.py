"""AOT artifact bundles: serialized compiled programs, sha-addressed.

The capture half of the libVeles analogue (docs/aot_artifacts.md): every
program a serving replica would otherwise trace + compile at boot — the
slot engine's admit/step/dispatch per (bucket, group) shape, dense and
paged, bf16 and int8/int8-KV, single-chip and per mesh layout — plus
the fused train step, exported through ``jax.export`` into StableHLO
and packed into a **versioned, sha-addressed bundle**:

- an uncompressed ustar tar (the same trivially-parseable format as the
  native runtime's packages, ``export.py``) whose members are
  **content-addressed**: ``programs/<sha256-of-bytes>``;
- ``manifest.json`` with one row per program — name (matching its
  ``observe/xla_stats`` instrumentation name), dispatch key, member
  sha, donated operands, static arguments, operand avals/shardings —
  plus the bundle-level compatibility fields the loader gates on:
  schema version, jax/jaxlib versions, the device fingerprint
  (``observe/regress.device_fingerprint``) and the mesh axes;
- a ``.sha256`` sidecar beside the tar, hashed through a write-tee
  (the snapshotter idiom — no second full-file read), which the forge
  upload path re-verifies on receipt.

Bundle bytes are deterministic (fixed epoch-0 mtimes, sorted members,
canonical JSON), so two builds of the same programs hash identically
and the sha-addressed store dedupes.

Programs cross the boundary in the **wire state format**
(``parallel/decode.wire_slot_state``): the ``req_key`` PRNG leaf rides
as raw uint32 data because jax.export's flatbuffer schema cannot
serialize extended key dtypes. The conversion is a bit-level
reinterpretation — wire streams are bit-identical to live ones.
"""

import functools
import inspect
import io
import json
import os
import tarfile

import numpy

MANIFEST = "manifest.json"
#: bundle schema — the loader refuses any other value by name
SCHEMA_VERSION = 1
BUNDLE_KIND = "veles-aot-bundle"


# -- export wrappers ---------------------------------------------------------
# One wire wrapper per captured program family: the live raw function
# (ONE copy of the math — the bit-identity contract) bracketed by the
# req_key wire conversion. Statics ride in as keyword-baked partials.

def _wire_admit(params, embed_table, state, slots, x, keys_data,
                lengths, *, heads):
    import jax
    from veles_tpu.parallel import decode

    state = decode.unwire_slot_state(state)
    out = decode._slot_admit_many(
        params, embed_table, heads, state, slots, x,
        jax.random.wrap_key_data(keys_data), lengths)
    return decode.wire_slot_state(out)


def _wire_step(params, embed_table, state, active, temperature, *,
               heads, sample, top_k, span):
    from veles_tpu.parallel import decode

    state = decode.unwire_slot_state(state)
    out, emitted = decode._slot_step(
        params, embed_table, heads, state, active, temperature,
        sample, top_k, span=span)
    return decode.wire_slot_state(out), emitted


def _wire_step_many(params, embed_table, state, active, temperature, *,
                    heads, n, sample, top_k, span):
    from veles_tpu.parallel import decode

    state = decode.unwire_slot_state(state)
    out, emitted = decode._slot_step_many(
        params, embed_table, heads, state, active, n, temperature,
        sample, top_k, span=span)
    return decode.wire_slot_state(out), emitted


def _wire_paged_admit(params, embed_table, state, slots, page_ids, x,
                      keys_data, lengths, *, heads):
    import jax
    from veles_tpu.parallel import decode, kv_pool

    state = decode.unwire_slot_state(state)
    out = kv_pool._paged_admit_many(
        params, embed_table, heads, state, slots, page_ids, x,
        jax.random.wrap_key_data(keys_data), lengths)
    return decode.wire_slot_state(out)


def _wire_paged_hit(state, slots, lengths, logits, keys_data):
    import jax
    from veles_tpu.parallel import decode, kv_pool

    state = decode.unwire_slot_state(state)
    out = kv_pool._paged_admit_hit(
        state, slots, lengths, logits,
        jax.random.wrap_key_data(keys_data))
    return decode.wire_slot_state(out)


def _wire_paged_step(params, embed_table, state, page_table, active,
                     temperature, *, heads, sample, top_k):
    from veles_tpu.parallel import decode, kv_pool

    state = decode.unwire_slot_state(state)
    out, emitted = kv_pool._paged_slot_step(
        params, embed_table, heads, state, page_table, active,
        temperature, sample, top_k)
    return decode.wire_slot_state(out), emitted


def _wire_paged_step_many(params, embed_table, state, page_table,
                          active, temperature, *, heads, n, sample,
                          top_k):
    from veles_tpu.parallel import decode, kv_pool

    state = decode.unwire_slot_state(state)
    out, emitted = kv_pool._paged_slot_step_many(
        params, embed_table, heads, state, page_table, active, n,
        temperature, sample, top_k)
    return decode.wire_slot_state(out), emitted


# -- aval plumbing -----------------------------------------------------------

def _avalify(args, mesh=None):
    """Operand skeletons for export: arrays become ShapeDtypeStructs
    keeping their shardings, except SingleDeviceSharding which (under a
    mesh) is replaced by the replicated mesh sharding — a host-staged
    control operand must not pin the whole lowering to device 0 (the
    ``xla_stats.abstractify`` doctrine)."""
    import jax
    from jax.sharding import (NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    repl = NamedSharding(mesh, PartitionSpec()) if mesh is not None \
        else None

    def conv(a):
        if not (hasattr(a, "shape") and hasattr(a, "dtype")):
            return a
        sharding = getattr(a, "sharding", None)
        if sharding is None or isinstance(sharding,
                                          SingleDeviceSharding):
            sharding = repl
        try:
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=sharding)
        except (TypeError, ValueError):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

    return jax.tree.map(conv, args)


def _strip_debug_info(exported):
    """Re-serialize an Exported's StableHLO without debug locations.

    The MLIR module embeds the full Python traceback of every op —
    including the BUILDER's own call site — so two otherwise identical
    exports from different scripts (or different lines of one script)
    would hash differently and defeat the sha-addressed store's dedup.
    ``strip-debuginfo`` removes exactly that, through jax's own
    portable-artifact recipe so the stripped module round-trips
    ``deserialize`` unchanged. Falls back to the original (correct,
    just caller-location-flavored) bytes if the pass is unavailable."""
    import dataclasses

    try:
        from jax._src.export import _export as jexport
        from jax._src.interpreters import mlir as jmlir
        from jaxlib.mlir import ir
        from jaxlib.mlir.passmanager import PassManager

        with jmlir.make_ir_context():
            module = ir.Module.parse(exported.mlir_module())
            PassManager.parse(
                "builtin.module(strip-debuginfo)").run(module.operation)
            stripped = jexport._module_to_bytecode(module)
        return dataclasses.replace(exported,
                                   mlir_module_serialized=stripped)
    except Exception:
        import logging
        logging.getLogger("aot").warning(
            "strip-debuginfo unavailable: bundle bytes will embed "
            "builder source locations (dedup across build sites "
            "degrades; programs stay correct)", exc_info=True)
        return exported


def _aval_rows(avals):
    """Human-readable manifest record of a program's operand avals:
    shape, dtype, partition spec and device layout. The layout is the
    platform's default (""): ``jax.export`` carries none, so a decoder
    bound to a bundle keeps its slab in it (serving.py)."""
    import jax

    rows = []
    for leaf in jax.tree.leaves(avals):
        if hasattr(leaf, "shape"):
            sharding = getattr(leaf, "sharding", None)
            layout = getattr(getattr(leaf, "format", None), "layout",
                             None)
            rows.append([list(leaf.shape), str(leaf.dtype),
                         str(getattr(sharding, "spec", ""))
                         if sharding is not None else "",
                         "" if layout is None else str(layout)])
    return rows


# -- the builder -------------------------------------------------------------

class BundleBuilder:
    """Accumulate exported programs, then write one deterministic
    sha-addressed bundle. ``meta`` extends the manifest (the serving
    builder records the decoder geometry there)."""

    def __init__(self, meta=None, mesh=None):
        import jax
        import jaxlib
        from veles_tpu.observe.regress import device_fingerprint

        self.mesh = mesh
        self.programs = []     # manifest rows
        self.blobs = {}        # member name -> bytes
        self.manifest = {
            "kind": BUNDLE_KIND,
            "schema": SCHEMA_VERSION,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "fingerprint": device_fingerprint(),
            "mesh": (None if mesh is None
                     else {"axes": dict(mesh.shape),
                           "devices": mesh.devices.size}),
        }
        if meta:
            self.manifest.update(meta)

    def add(self, name, key, fn, args, donate=(), statics=None,
            out_shardings=None):
        """Export one program: ``fn`` is the RAW (unjitted) callable,
        ``args`` example operands (or avals), ``donate`` the donated
        parameter names, ``statics`` the keyword-baked static args.
        ``name`` must be the program's ``observe/xla_stats``
        instrumentation name — the loader books its calls under it."""
        import hashlib

        import jax
        from jax import export as jax_export

        statics = dict(statics or {})
        jit_kwargs = {}
        if donate:
            jit_kwargs["donate_argnames"] = tuple(donate)
        if out_shardings is not None:
            jit_kwargs["out_shardings"] = out_shardings
        jitted = jax.jit(functools.partial(fn, **statics), **jit_kwargs)
        avals = _avalify(args, mesh=self.mesh)
        exported = _strip_debug_info(jax_export.export(jitted)(*avals))
        blob = bytes(exported.serialize())
        digest = hashlib.sha256(blob).hexdigest()
        member = "programs/%s" % digest
        self.blobs[member] = blob
        # donated POSITIONS (what jit-of-the-deserialized-call wants):
        # resolved from the wrapper's signature, not guessed
        names = [p.name for p in
                 inspect.signature(fn).parameters.values()
                 if p.kind == p.POSITIONAL_OR_KEYWORD]
        self.programs.append({
            "name": name,
            "key": list(key),
            "member": member,
            "sha256": digest,
            "bytes": len(blob),
            "donate": [names.index(d) for d in donate],
            "statics": {k: (v if isinstance(v, (int, float, bool,
                                                str, type(None)))
                            else str(v)) for k, v in statics.items()},
            "in_avals": _aval_rows(avals),
        })
        return digest

    def write(self, path):
        """Write the bundle tar + its ``.sha256`` sidecar. Bytes are
        deterministic: fixed epoch-0 mtimes, zero uid/gid, members
        sorted, canonical manifest JSON — two builds of identical
        programs produce identical files, so the sha-addressed store
        dedupes (the determinism satellite's contract, shared with
        ``export.py``/``forge/package.py``)."""
        from veles_tpu.snapshotter import _HashingWriter

        manifest = dict(self.manifest,
                        programs=sorted(self.programs,
                                        key=lambda r: (r["name"],
                                                       r["key"])))
        payload = json.dumps(manifest, indent=1,
                             sort_keys=True).encode()
        members = [(MANIFEST, payload)]
        members += sorted(self.blobs.items())
        tmp = path + ".tmp%d" % os.getpid()
        with open(tmp, "wb") as raw:
            tee = _HashingWriter(raw)
            with tarfile.open(fileobj=tee, mode="w",
                              format=tarfile.USTAR_FORMAT) as tar:
                for name, blob in members:
                    info = tarfile.TarInfo(name)
                    info.size = len(blob)
                    info.mtime = 0
                    info.uid = info.gid = 0
                    info.uname = info.gname = ""
                    tar.addfile(info, io.BytesIO(blob))
            digest = tee.hexdigest()
        os.replace(tmp, path)
        sidecar = path + ".sha256"
        tmp = sidecar + ".tmp%d" % os.getpid()
        with open(tmp, "w") as fout:
            fout.write("%s  %s\n" % (digest, os.path.basename(path)))
        os.replace(tmp, sidecar)
        return path


# -- serving capture ---------------------------------------------------------

def _pow2_groups(slots):
    """The padded admission-group sizes the decoder can dispatch
    (``ContinuousDecoder._pad_group`` pads to powers of two)."""
    out, g = [], 1
    while g < slots:
        out.append(g)
        g *= 2
    out.append(g)
    return out


def _buckets(max_len):
    """``ContinuousDecoder._bucket``'s image: powers of two from 16,
    clamped to ``max_len``."""
    out, b = [], 16
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return sorted(set(out))


def _spans(tile, max_len):
    """``ContinuousDecoder._attended_span``'s image: multiples of the
    tile, clamped to ``max_len``."""
    out, s = [], tile
    while s < max_len:
        out.append(s)
        s += tile
    out.append(max_len)
    return sorted(set(out))


def decoder_geometry(dec):
    """The compatibility-gated shape identity of a decoder: everything
    that determines its programs' avals. The loader refuses a bundle
    whose geometry differs, naming the stale field."""
    return {
        "n_blocks": len(dec.params["blocks"]),
        "embed": int(dec.embed_table.shape[1]),
        "vocab": int(dec.embed_table.shape[0]),
        "heads": int(dec.heads),
        "dtype": str(dec.embed_table.dtype),
        "slots": int(dec.slots),
        "max_len": int(dec.max_len),
        "tile": int(dec.tile),
        "quantize": dec.quantize or "none",
        "paged": bool(dec.paged),
        "page_size": dec.page_size,
        "pool_pages": dec.pool_pages,
        "sample": bool(dec.temperature),
        "top_k": int(dec.top_k),
        "mesh_axis": dec.mesh_axis if dec.mesh is not None else None,
    }


def build_serving_bundle(params, embed_table, heads, path, *, slots=4,
                         max_len=512, n_tokens=32, chunk=8,
                         temperature=0.0, top_k=0, quantize=None,
                         tile=None, paged=False, page_size=None,
                         pool_pages=None, mesh=None, mesh_axis="model",
                         buckets=None, progress=None):
    """Capture every slot program a :class:`ContinuousDecoder` with
    this configuration dispatches — one export per ``(bucket, group)``
    admission shape, per attended span (dense) or pages-per-slot
    bucket (paged), plus the chunked dispatch at ``chunk`` and the
    single-step program — and write the bundle to ``path``.

    The geometry is derived from a real decoder built with the SAME
    kwargs (one construction, then discarded), so the captured avals
    can never drift from what serving actually dispatches — including
    the int8-KV tier's max_len rounding and the paged tier's pool
    sizing defaults.

    Paged note: the shared-prefix TAIL admission family is not
    enumerable ahead of time (its key includes the cached prefix's page
    count); tail admissions fall back to live compilation at the
    loader's dispatch seam — never a wrong answer, counted in
    ``veles_aot_misses_total``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veles_tpu.parallel import decode
    from veles_tpu.serving import ContinuousDecoder

    dec = ContinuousDecoder(
        params, embed_table, heads, slots=slots, max_len=max_len,
        n_tokens=n_tokens, temperature=temperature, top_k=top_k,
        quantize=quantize, tile=tile, mesh=mesh, mesh_axis=mesh_axis,
        paged=paged, page_size=page_size, pool_pages=pool_pages)
    geometry = decoder_geometry(dec)
    builder = BundleBuilder(
        meta={"geometry": geometry, "chunk": int(chunk),
              "n_tokens": int(n_tokens)},
        mesh=dec.mesh)
    quantized = dec.quantize == "int8-kv"
    sample = bool(dec.temperature)
    statics_base = {"heads": int(dec.heads)}
    wire_state = decode.wire_slot_state(dec.state)
    out_state_sh = None
    out_pair_sh = None
    if dec.mesh is not None:
        if dec.paged:
            from veles_tpu.parallel.kv_pool import paged_state_specs
            specs = paged_state_specs(quantized, axis=dec.mesh_axis)
        else:
            specs = decode.slot_state_specs(
                len(dec.params["blocks"]), quantized,
                axis=dec.mesh_axis)
        out_state_sh = jax.tree.map(
            lambda spec: NamedSharding(dec.mesh, spec), specs)
        replicated = NamedSharding(dec.mesh, P())
        out_pair_sh = (out_state_sh, replicated)
    table = dec.embed_table
    dtype = table.dtype
    embed = table.shape[1]
    vocab = table.shape[0]

    def keys_data(n):
        keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            dec.base_key, jnp.arange(n, dtype=jnp.int32))
        return jax.random.key_data(keys)

    def note(name, key):
        if progress is not None:
            progress(name, key)

    group_sizes = _pow2_groups(dec.slots)
    bucket_sizes = buckets or _buckets(dec.max_len)
    if dec.paged:
        from veles_tpu.parallel import kv_pool
        ps = dec.page_size
        for bucket in bucket_sizes:
            np_pages = kv_pool.pages_for(bucket, ps)
            for group in group_sizes:
                key = ("paged_admit", bucket, group, np_pages)
                note("paged.admit", key)
                builder.add(
                    "paged.admit", key, _wire_paged_admit,
                    (dec.params, table, wire_state,
                     jnp.zeros((group,), jnp.int32),
                     jnp.zeros((group, np_pages), jnp.int32),
                     jnp.zeros((group, bucket, embed), dtype),
                     keys_data(group),
                     jnp.zeros((group,), jnp.int32)),
                    donate=("state",), statics=statics_base,
                    out_shardings=out_state_sh)
        for group in group_sizes:
            key = ("paged_hit", group)
            note("paged.admit_hit", key)
            builder.add(
                "paged.admit_hit", key, _wire_paged_hit,
                (wire_state, jnp.zeros((group,), jnp.int32),
                 jnp.zeros((group,), jnp.int32),
                 jnp.zeros((group, vocab), jnp.float32),
                 keys_data(group)),
                donate=("state",), statics={},
                out_shardings=out_state_sh)
        # the lag-1 pipeline's overshoot bound (default_pool_pages'
        # own sizing doctrine): a live lane can stand at
        # max_len - 1 + chunk after an overshoot dispatch and the next
        # _page_table_array(chunk) adds another chunk — enumerating
        # only to max_len + chunk would live-compile the LARGEST
        # paged program mid-serving, exactly when the pipeline is
        # deepest
        pb_max = kv_pool.pages_for(dec.max_len - 1 + 2 * int(chunk),
                                   ps)
        step_statics = dict(statics_base, sample=sample,
                            top_k=int(dec.top_k))
        for pb in range(1, pb_max + 1):
            table_arg = jnp.zeros((dec.slots, pb), jnp.int32)
            active = jnp.zeros((dec.slots,), bool)
            key = ("paged_step", pb)
            note("paged.step", key)
            builder.add(
                "paged.step", key, _wire_paged_step,
                (dec.params, table, wire_state, table_arg, active,
                 jnp.float32(1.0)),
                donate=("state",), statics=step_statics,
                out_shardings=out_pair_sh)
            key = ("paged_dispatch", int(chunk), pb)
            note("paged.dispatch", key)
            builder.add(
                "paged.dispatch", key, _wire_paged_step_many,
                (dec.params, table, wire_state, table_arg, active,
                 jnp.float32(1.0)),
                donate=("state",),
                statics=dict(step_statics, n=int(chunk)),
                out_shardings=out_pair_sh)
    else:
        for bucket in bucket_sizes:
            for group in group_sizes:
                key = ("admit", bucket, group)
                note("decode.admit", key)
                builder.add(
                    "decode.admit", key, _wire_admit,
                    (dec.params, table, wire_state,
                     jnp.zeros((group,), jnp.int32),
                     jnp.zeros((group, bucket, embed), dtype),
                     keys_data(group),
                     jnp.zeros((group,), jnp.int32)),
                    donate=("state",), statics=statics_base,
                    out_shardings=out_state_sh)
        step_statics = dict(statics_base, sample=sample,
                            top_k=int(dec.top_k))
        for span in _spans(dec.tile, dec.max_len):
            active = jnp.zeros((dec.slots,), bool)
            key = ("step", span)
            note("decode.step", key)
            builder.add(
                "decode.step", key, _wire_step,
                (dec.params, table, wire_state, active,
                 jnp.float32(1.0)),
                donate=("state",),
                statics=dict(step_statics, span=span),
                out_shardings=out_pair_sh)
            key = ("dispatch", int(chunk), span)
            note("decode.dispatch", key)
            builder.add(
                "decode.dispatch", key, _wire_step_many,
                (dec.params, table, wire_state, active,
                 jnp.float32(1.0)),
                donate=("state",),
                statics=dict(step_statics, n=int(chunk), span=span),
                out_shardings=out_pair_sh)
    return builder.write(path)


def capture_tick_programs(builder, steps, train_args, eval_args=None):
    """Capture the fused training tick (``parallel/fused.build_tick``
    output) into ``builder``: the train step (donating its params, as
    the live tick does) and optionally the eval step. ``train_args``/
    ``eval_args`` are one example argument tuple each — only their
    shapes/dtypes are read. Keyed by the minibatch size so a loaded
    bundle dispatches per shape exactly like the serving programs."""
    train_step, eval_step = steps[0], steps[1]
    mb = int(numpy.shape(train_args[5])[0])  # indices (mb,)

    # the steps are already jitted; the wrapper jit inlines them and
    # re-declares the donation at the export boundary
    def raw_train(params, hypers, norm, data, labels, indices, valid,
                  seed):
        return train_step(params, hypers, norm, data, labels, indices,
                          valid, seed)

    builder.add("fused.train_step", ("train_step", mb), raw_train,
                tuple(train_args), donate=("params",), statics={})
    if eval_args is not None:
        def raw_eval(params, norm, data, labels, indices, valid):
            return eval_step(params, norm, data, labels, indices,
                             valid)

        builder.add("fused.eval_step",
                    ("eval_step", int(numpy.shape(eval_args[4])[0])),
                    raw_eval, tuple(eval_args), statics={})
    return builder


# -- reading -----------------------------------------------------------------

def read_bundle(path, verify=True):
    """Read a bundle: returns ``(manifest, members)`` with ``members``
    a {name: bytes} dict. ``verify`` checks the ``.sha256`` sidecar
    (when present) and every program member's content hash against its
    sha-addressed name + manifest row — a tampered or torn bundle
    raises ``ValueError`` naming the bad member, never loads."""
    import hashlib

    if verify:
        sidecar = path + ".sha256"
        if os.path.isfile(sidecar):
            from veles_tpu.observe.regress import sha256_of
            with open(sidecar) as fin:
                fields = fin.read().split()
            if not fields or fields[0] != sha256_of(path):
                raise ValueError(
                    "%s does not match its .sha256 sidecar" % path)
    members = {}
    try:
        with tarfile.open(path, "r") as tar:
            for member in tar.getmembers():
                if member.isfile():
                    members[member.name] = \
                        tar.extractfile(member).read()
    except tarfile.TarError as exc:
        # keep the documented ValueError contract: tarfile.ReadError
        # inherits Exception directly, and the serving fallback / CLI
        # exit-2 paths catch (ValueError, OSError)
        raise ValueError("%s is not a readable bundle tar: %s"
                         % (path, exc))
    if MANIFEST not in members:
        raise ValueError("%s has no %s" % (path, MANIFEST))
    try:
        manifest = json.loads(members[MANIFEST].decode())
    except ValueError:
        raise ValueError("%s: manifest.json is not valid JSON" % path)
    if manifest.get("kind") != BUNDLE_KIND:
        raise ValueError("%s is not a %s (kind=%r)"
                         % (path, BUNDLE_KIND, manifest.get("kind")))
    if verify:
        for row in manifest.get("programs", ()):
            blob = members.get(row["member"])
            if blob is None:
                raise ValueError("%s: manifest names missing member %s"
                                 % (path, row["member"]))
            digest = hashlib.sha256(blob).hexdigest()
            if digest != row["sha256"] \
                    or not row["member"].endswith(digest):
                raise ValueError(
                    "%s: member %s content hash %s does not match its "
                    "sha-addressed name" % (path, row["member"],
                                            digest))
    return manifest, members


def inspect_bundle(path):
    """Manifest summary for ``veles_tpu aot inspect``."""
    manifest, members = read_bundle(path, verify=False)
    programs = manifest.get("programs", [])
    by_name = {}
    for row in programs:
        entry = by_name.setdefault(row["name"],
                                   {"programs": 0, "bytes": 0})
        entry["programs"] += 1
        entry["bytes"] += row["bytes"]
    return {
        "path": path,
        "schema": manifest.get("schema"),
        "jax": manifest.get("jax"),
        "jaxlib": manifest.get("jaxlib"),
        "fingerprint": manifest.get("fingerprint"),
        "mesh": manifest.get("mesh"),
        "geometry": manifest.get("geometry"),
        "chunk": manifest.get("chunk"),
        "programs": len(programs),
        "by_name": by_name,
        "total_bytes": sum(r["bytes"] for r in programs),
    }
