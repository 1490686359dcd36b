"""Logical device mesh construction.

Axes (sized from ``root.common.mesh.axes``, -1 = absorb remaining devices):

- ``data``  — batch (DP); gradient psum rides ICI
- ``model`` — tensor parallel (TP): weight column/row shards
- ``seq``   — sequence/context parallel (ring attention neighborhoods)
- ``pipe``  — pipeline stages
- ``expert``— MoE expert parallel

The reference has no analogue (its DP is host-level); this is the
scaling-book-style mesh the whole pod-mode design hangs off.
"""

import threading

import numpy

import jax
from jax.sharding import Mesh

from veles_tpu.core.config import root

AXIS_ORDER = ("pipe", "data", "expert", "seq", "model")


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off. Every shard_map
    in the tree routes through here so the one setting they share is
    one edit, not eight."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def mesh_axes():
    cfg = root.common.mesh.axes
    if hasattr(cfg, "__content__"):
        cfg = cfg.__content__()
    return {name: int(cfg.get(name, 1)) for name in AXIS_ORDER}


def mesh_configured():
    """True when the config asks for a non-trivial mesh (any axis != 1,
    including a -1 absorb-the-devices wildcard). This is what makes pod
    mode CLI-reachable: ``--mesh data=8`` / ``root.common.mesh.axes``
    sets it, and the launcher then builds the mesh into the workflow."""
    return any(v != 1 for v in mesh_axes().values())


def initialize_distributed(coordinator, num_processes, process_id,
                           local_device_count=None):
    """Multi-host pod bring-up: ``jax.distributed.initialize`` so every
    process sees the GLOBAL device list and ``build_mesh`` spans hosts.

    The reference reached across hosts by SSH-spawning slaves and
    selecting per-host endpoints (``launcher.py:617-660``,
    ``server.py:721-732``); the TPU-idiomatic equivalent is one SPMD
    program per host joined through the JAX coordination service, with
    XLA collectives riding ICI/DCN. Must run before any jax backend
    initializes (i.e. before the first ``jax.devices()`` call).

    ``local_device_count`` (CPU testing only) forces this process's
    virtual device count via XLA_FLAGS — on real TPU hosts leave unset.
    """
    import os
    if local_device_count:
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append("--xla_force_host_platform_device_count=%d"
                     % local_device_count)
        os.environ["XLA_FLAGS"] = " ".join(flags)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=int(num_processes),
                               process_id=int(process_id))


def is_primary():
    """True on the process that owns singleton side effects (snapshots,
    plots, web status, result files) in a multi-process pod. Single
    process → trivially True; does not force jax backend init order
    beyond what any device query would."""
    try:
        return jax.process_index() == 0
    except RuntimeError:
        return True


def parse_axes(spec, flag="--mesh"):
    """Parse an ``AXIS=N[,AXIS=N...]`` mesh string into an override
    dict — ONE parser for ``--mesh`` and ``--serve-mesh`` (and their
    config twins), so the syntax cannot drift between flags. Raises
    ``ValueError`` naming ``flag``; sizes stay unvalidated here —
    :func:`build_mesh` owns the integer/positivity checks."""
    overrides = {}
    for part in str(spec).split(","):
        axis, eq, size = part.partition("=")
        axis = axis.strip()
        if not eq or axis not in AXIS_ORDER:
            raise ValueError(
                "%s expects AXIS=N[,AXIS=N...] with axes from %s, "
                "got %r" % (flag, ", ".join(AXIS_ORDER), spec))
        try:
            overrides[axis] = int(size)
        except ValueError:
            raise ValueError("%s: size %r of axis %s is not an integer"
                             % (flag, size, axis))
    return overrides


def build_mesh(devices=None, flag="root.common.mesh.axes / --mesh",
               **overrides):
    """Build a Mesh over ``devices`` with configured axis sizes.

    Axis sizes multiply to the device count; a single -1 axis absorbs the
    remainder (like a reshape). Axes of size 1 are kept (they cost nothing
    and make in/out specs uniform).

    Every size is validated here with an error naming the config knob —
    ``flag`` (the training default, or ``--serve-mesh``'s twin via
    :func:`veles_tpu.serving.build_serve_mesh`) — a bad value must fail
    as "axis data=0 is invalid", never as an opaque numpy reshape
    exception three layers down.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    sizes = mesh_axes()
    for key, value in overrides.items():
        if key not in sizes:
            raise ValueError(
                "unknown mesh axis %r (valid: %s) — check %s"
                % (key, ", ".join(AXIS_ORDER), flag))
        sizes[key] = value
    for key, value in sizes.items():
        try:
            as_int = int(value)
        except (TypeError, ValueError):
            as_int = None
        if as_int is None or as_int != value or (
                as_int < 1 and as_int != -1):
            raise ValueError(
                "mesh axis %s=%r is invalid: sizes must be positive "
                "integers (or -1 to absorb the remaining devices) — "
                "check %s" % (key, value, flag))
        sizes[key] = as_int
    wildcard = [k for k, v in sizes.items() if v == -1]
    fixed = int(numpy.prod([v for v in sizes.values() if v != -1]))
    if len(wildcard) > 1:
        raise ValueError("only one mesh axis may be -1, got %s" % wildcard)
    if wildcard:
        if n % fixed:
            raise ValueError(
                "mesh axes %s: the fixed sizes multiply to %d, which "
                "does not divide the %d available devices — check %s"
                % (sizes, fixed, n, flag))
        sizes[wildcard[0]] = n // fixed
    elif fixed != n:
        raise ValueError(
            "mesh axes %s multiply to %d but %d devices present — "
            "check %s" % (sizes, fixed, n, flag))
    shape = tuple(sizes[name] for name in AXIS_ORDER)
    dev_array = numpy.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, AXIS_ORDER)
    note_active_mesh(mesh)
    return mesh


# -- active-mesh registry ----------------------------------------------------
#
# The LAST mesh built in this process, kept as plain data (no Device
# refs): the /metrics mesh gauges, the web-status device column and the
# fleet slaves' metric-row coordinates all read it (a master scrape must
# be able to tell WHICH shard a process is, not just which slave).

_active_lock = threading.Lock()
_active_mesh = None


def note_active_mesh(mesh):
    """Record ``mesh`` as the process's active mesh (called by
    :func:`build_mesh`; callers constructing a Mesh by hand can call it
    directly)."""
    global _active_mesh
    info = {"axes": {name: int(size)
                     for name, size in dict(mesh.shape).items()},
            "devices": int(mesh.size)}
    with _active_lock:
        _active_mesh = info


def active_mesh_info():
    """``{"axes": {name: size}, "devices": n}`` of the last mesh built
    in this process, or None when nothing meshed yet."""
    with _active_lock:
        return None if _active_mesh is None else {
            "axes": dict(_active_mesh["axes"]),
            "devices": _active_mesh["devices"]}


def mesh_shape_label(info=None):
    """Compact ``data2.model4`` string of the non-trivial axes (label
    value for /metrics rows and the dashboard cell); None when no mesh
    is active or every axis is 1."""
    if info is None:
        info = active_mesh_info()
    if not info:
        return None
    parts = ["%s%d" % (name, size)
             for name in AXIS_ORDER
             for size in [info["axes"].get(name, 1)] if size != 1]
    return ".".join(parts) or None


def mesh_coordinate_labels():
    """Label dict identifying this process's place in the pod:
    ``{"process": i, "mesh": "data2.model4"}`` — merged into the
    metric rows a fleet slave piggybacks on update frames so a master
    scrape distinguishes shards, not just slaves. Empty when no mesh
    is active (single-chip slaves keep their old label set)."""
    label = mesh_shape_label()
    if label is None:
        return {}
    try:
        process = jax.process_index()
    except RuntimeError:
        process = 0
    return {"process": str(process), "mesh": label}
