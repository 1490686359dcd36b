"""Portable train↔serve resharding: collective schedules, not device_put.

The mesh gives one checkpoint two natural layouts — the fused train
step wants params replicated over ``data`` (gradients psum over ICI),
the slot-engine serving tier wants them tensor-parallel over ``model``
with the KV cache sharded by head. Moving between them with a naive
``jax.device_put`` round-trips every shard through a host-mediated
copy-and-rescatter; *Memory-efficient array redistribution through
portable collective communication* (arxiv 2112.01075) shows any
``PartitionSpec`` change decomposes into a short schedule of portable
collectives that stays on the interconnect. This module implements
that decomposition:

- a mesh axis that moves BETWEEN tensor dims (``P(None, "model")`` →
  ``P("model", None)``) is one ``all_to_all`` — each device keeps
  ``1/n`` of its shard and exchanges the rest, never materializing the
  full array (the paper's headline saving over gather-then-slice);
- an axis only in the SOURCE spec is an ``all_gather`` along its dim;
- an axis only in the DESTINATION spec is a local ``dynamic_slice`` at
  the device's axis index (zero bytes on the wire).

Steps run in that order (all-to-alls first keep peak memory at the
shard size for the transpose-resharding case); values are moved, never
recomputed, so a round trip is bit-exact. Every call is measured:
per-transition bytes-on-the-wire and wall seconds land in the metrics
registry (``veles_reshard_bytes_total`` / ``veles_reshard_seconds`` —
docs/sharded_serving.md).
"""

import threading
import time

import numpy

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from veles_tpu.parallel.mesh import shard_map

#: reshard-latency histogram buckets (seconds): intra-host CPU test
#: meshes through cross-pod transitions of multi-GiB param trees
RESHARD_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                   0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


def _axis_dims(spec, ndim):
    """{mesh axis name: tensor dim} of a PartitionSpec (tuple entries —
    several axes sharding one dim — map each axis to that dim)."""
    out = {}
    for dim, entry in enumerate(tuple(spec)[:ndim]):
        if entry is None:
            continue
        for name in (entry if isinstance(entry, tuple) else (entry,)):
            out[name] = dim
    return out


def _normalize_spec(spec):
    """Canonical PartitionSpec: unsharded entries become None and
    trailing Nones are stripped, so specs that SPELL the same layout
    differently (``P("model")`` vs ``P("model", None)``, ``P()`` vs
    ``P(None)``, a 1-tuple axis entry vs the bare name) compare equal —
    the keep/schedule decision below must see layouts, not spellings
    (jax reports live arrays' specs in any of these forms)."""
    if spec is None:
        return PartitionSpec()
    if isinstance(spec, NamedSharding):
        spec = spec.spec
    entries = []
    for entry in tuple(spec):
        if isinstance(entry, tuple):
            entry = entry[0] if len(entry) == 1 else (entry or None)
        entries.append(entry)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def _divisible(shape, spec, sizes):
    for dim, entry in enumerate(tuple(spec)[:len(shape)]):
        if entry is None:
            continue
        total = 1
        for name in (entry if isinstance(entry, tuple) else (entry,)):
            total *= sizes[name]
        if shape[dim] % total:
            return False
    return True


def _dim_entries(spec, ndim):
    """Per-dim tuple of sharding axes (major → minor), length ndim."""
    out = []
    entries = tuple(spec)[:ndim]
    for dim in range(ndim):
        entry = entries[dim] if dim < len(entries) else None
        if entry is None:
            out.append(())
        elif isinstance(entry, tuple):
            out.append(tuple(entry))
        else:
            out.append((entry,))
    return out


class LeafPlan:
    """The collective schedule for ONE array's spec change.

    ``steps`` is a list of ``(kind, axis, src_dim, dst_dim)`` with kind
    in ``all_to_all`` / ``all_gather`` / ``slice`` / ``keep``.
    An axis moving between dims rides ONE all_to_all (the paper's
    memory-bounded transpose resharding) when the move is CLEAN — the
    axis is alone on both its source and destination dim, and the
    destination dim is unsharded in the source layout; any other
    transition lowers to the always-correct gather-then-slice form
    (gathers per dim minor-axis-first, slices major-axis-first, so
    nested tuple shardings reassemble in index order). ``bytes`` is the
    total crossing the interconnect, summed over devices (all-to-all:
    ``(n-1)/n`` of each device's shard; all-gather: ``n-1`` shards
    received per device; slice/keep: zero)."""

    __slots__ = ("shape", "dtype", "src", "dst", "steps", "bytes")

    def __init__(self, shape, dtype, src, dst, sizes, n_devices):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.src = src
        self.dst = dst
        self.steps = []
        self.bytes = 0
        nbytes = int(numpy.prod(shape, dtype=numpy.int64)
                     * numpy.dtype(dtype).itemsize) if shape else \
            numpy.dtype(dtype).itemsize
        if src == dst:
            self.steps.append(("keep", None, None, None))
            return
        for name, spec in (("source", src), ("destination", dst)):
            if not _divisible(shape, spec, sizes):
                raise ValueError(
                    "reshard: shape %s cannot shard as %s spec %s — "
                    "every sharded dim must divide by its mesh axis "
                    "size(s) %s" % (list(shape), name, spec,
                                    dict(sizes)))
        ndim = len(shape)
        s_dims = _dim_entries(src, ndim)
        d_dims = _dim_entries(dst, ndim)
        s = _axis_dims(src, ndim)
        d = _axis_dims(dst, ndim)
        live = {ax: sizes[ax] for ax in s}  # axes currently sharding

        def local_bytes():
            return nbytes // int(numpy.prod(
                list(live.values()) or [1], dtype=numpy.int64))

        # 1) clean single-axis moves: one all_to_all each. "Clean" =
        #    the axis is alone on its src and dst dims and the dst dim
        #    carries no src sharding, so the tiled split/concat IS the
        #    layout change. Each device exchanges (n-1)/n of its shard
        #    inside its axis group.
        a2a = []
        for ax in sorted(set(s) & set(d)):
            if s[ax] == d[ax]:
                continue
            if (s_dims[s[ax]] == (ax,) and d_dims[d[ax]] == (ax,)
                    and not s_dims[d[ax]]):
                n = sizes[ax]
                self.bytes += n_devices * local_bytes() * (n - 1) // n
                self.steps.append(("all_to_all", ax, s[ax], d[ax]))
                a2a.append(ax)
        # 2) everything else lowers to gather + slice, scheduled
        #    per-dim so nested tuple shardings reassemble in global
        #    index order: gathers must peel a dim's MINOR suffix
        #    (tiled all_gather concatenates group order along the
        #    dim), slices must add a MINOR suffix under the staying
        #    prefix. A dim whose change is not suffix-shaped (axis
        #    swaps inside a tuple, a major axis leaving under a
        #    staying minor one) escalates: the whole dim gathers to
        #    full and reslices — always correct, the paper's portable
        #    lower bound when no cheaper schedule applies.
        gathers, slices = [], []
        for dim in range(ndim):
            leaving = tuple(ax for ax in s_dims[dim]
                            if ax not in a2a
                            and (ax not in d or d[ax] != dim))
            arriving = tuple(ax for ax in d_dims[dim]
                             if ax not in a2a
                             and (ax not in s or s[ax] != dim))
            if not leaving and not arriving:
                continue
            src_stay = tuple(ax for ax in s_dims[dim]
                             if ax not in leaving and ax not in a2a)
            dst_stay = tuple(ax for ax in d_dims[dim]
                             if ax not in arriving and ax not in a2a)
            suffix_ok = (
                src_stay == dst_stay
                and s_dims[dim][:len(src_stay)] == src_stay
                and d_dims[dim][:len(dst_stay)] == dst_stay)
            if suffix_ok:
                gathers.append((dim, leaving))
                slices.append((dim, arriving))
            else:
                gathers.append((dim, tuple(
                    ax for ax in s_dims[dim] if ax not in a2a)))
                slices.append((dim, tuple(
                    ax for ax in d_dims[dim] if ax not in a2a)))
        for dim, leaving in gathers:
            # minor-axis-first: each gather concatenates its groups
            # back into global index order under the remaining prefix
            for ax in reversed(leaving):
                n = sizes[ax]
                self.bytes += n_devices * local_bytes() * (n - 1)
                self.steps.append(("all_gather", ax, dim, None))
                del live[ax]
        for dim, arriving in slices:
            # major-axis-first: sequential slices nest correctly
            for ax in arriving:
                self.steps.append(("slice", ax, None, dim))
        if not self.steps:
            # src != dst as objects but no axis moved — the layouts
            # were equal under a spelling _normalize_spec didn't fold;
            # an empty schedule IS a keep, never an indexing crash
            self.steps.append(("keep", None, None, None))

    def describe(self):
        return {"shape": list(self.shape),
                "dtype": str(numpy.dtype(self.dtype)),
                "src": str(self.src), "dst": str(self.dst),
                "bytes": self.bytes,
                "steps": [{"op": op, "axis": ax,
                           "src_dim": sd, "dst_dim": dd}
                          for op, ax, sd, dd in self.steps]}


class ReshardPlan:
    """The whole tree's transition: per-leaf :class:`LeafPlan` list in
    flatten order, total wire bytes, and the step-kind tally the tests
    pin (a transpose resharding must plan all-to-all, never
    gather+slice)."""

    def __init__(self, leaves):
        self.leaves = leaves
        self.bytes = sum(leaf.bytes for leaf in leaves)

    def counts(self):
        out = {}
        for leaf in self.leaves:
            for op, *_ in leaf.steps:
                out[op] = out.get(op, 0) + 1
        return out

    def describe(self):
        return {"bytes": self.bytes, "counts": self.counts(),
                "leaves": [leaf.describe() for leaf in self.leaves]}


def _build_plan(leaves, src_list, dst_list, mesh):
    sizes = dict(mesh.shape)
    return ReshardPlan([
        LeafPlan(leaf.shape, leaf.dtype, src, dst, sizes, mesh.size)
        for leaf, src, dst in zip(leaves, src_list, dst_list)])


def plan_reshard(tree, mesh, dst_specs, src_specs):
    """Build the :class:`ReshardPlan` for moving ``tree`` from
    ``src_specs`` to ``dst_specs`` over ``mesh`` (specs: a matching
    pytree of ``PartitionSpec``, or one spec broadcast to every leaf)."""
    leaves, treedef = jax.tree.flatten(tree)
    src_list = _spec_list(src_specs, leaves, treedef)
    dst_list = _spec_list(dst_specs, leaves, treedef)
    return _build_plan(leaves, src_list, dst_list, mesh)


def _spec_list(specs, leaves, treedef):
    if isinstance(specs, (PartitionSpec, NamedSharding)) or specs is None:
        return [_normalize_spec(specs)] * len(leaves)
    flat = treedef.flatten_up_to(specs)
    return [_normalize_spec(spec) for spec in flat]


def _leaf_body(plan, sizes):
    """shard_map-local function applying one leaf's schedule."""
    def body(x):
        for kind, ax, src_dim, dst_dim in plan.steps:
            if kind == "all_to_all":
                x = lax.all_to_all(x, ax, split_axis=dst_dim,
                                   concat_axis=src_dim, tiled=True)
            elif kind == "all_gather":
                x = lax.all_gather(x, ax, axis=src_dim, tiled=True)
            elif kind == "slice":
                chunk = x.shape[dst_dim] // sizes[ax]
                x = lax.dynamic_slice_in_dim(
                    x, lax.axis_index(ax) * chunk, chunk, axis=dst_dim)
        return x
    return body


#: (mesh, structure/shape/spec signature) -> compiled transition. ONE
#: program per distinct transition, so repeated train↔serve flips hit
#: the jit cache (and the instrument() compile counters see one
#: compile, not one per call). _PLAN_CACHE shares the key (sans
#: schedule subset): the pure-Python schedule is fully determined by
#: it, so repeated flips skip the O(leaves × ndim) planning too.
_FN_CACHE = {}
_PLAN_CACHE = {}
_FN_LOCK = threading.Lock()


def _cache_key(mesh, treedef, leaves, src_list, dst_list):
    return (mesh, treedef,
            tuple((leaf.shape, str(leaf.dtype)) for leaf in leaves),
            tuple(str(s) for s in src_list),
            tuple(str(d) for d in dst_list))


def reshard(tree, mesh, dst_specs, src_specs=None, label="reshard",
            registry=None):
    """Move ``tree`` from its current sharding to ``dst_specs`` via the
    collective schedule; returns ``(new_tree, stats)``.

    ``dst_specs`` / ``src_specs``: a pytree of ``PartitionSpec``
    matching ``tree``, or one spec broadcast to every leaf.
    ``src_specs=None`` reads each leaf's current ``NamedSharding`` spec
    (leaves not already sharded over ``mesh`` — fresh host arrays,
    single-device results — are treated as replicated and placed first).
    ``stats``: ``{"bytes", "seconds", "counts"}``; the same numbers
    land on the metrics registry as ``veles_reshard_bytes_total`` /
    ``veles_reshard_seconds`` labeled by ``label`` (the train→serve /
    serve→train transitions each carry their own label on /metrics).

    Bit-exactness: every step is a data movement (exchange, gather,
    slice) — no arithmetic — so ``reshard(reshard(x, serve), train)``
    returns ``x``'s values exactly, which ``tests/test_reshard.py``
    asserts for arbitrary spec pairs.
    """
    leaves, treedef = jax.tree.flatten(tree)
    dst_list = _spec_list(dst_specs, leaves, treedef)
    if src_specs is None:
        src_list = []
        for leaf in leaves:
            sharding = getattr(leaf, "sharding", None)
            if isinstance(sharding, NamedSharding) \
                    and sharding.mesh == mesh:
                src_list.append(_normalize_spec(sharding.spec))
            else:
                src_list.append(PartitionSpec())
    else:
        src_list = _spec_list(src_specs, leaves, treedef)
    plan_key = _cache_key(mesh, treedef, leaves, src_list, dst_list)
    with _FN_LOCK:
        plan = _PLAN_CACHE.get(plan_key)
    if plan is None:
        plan = _build_plan(leaves, src_list, dst_list, mesh)
        with _FN_LOCK:
            _PLAN_CACHE[plan_key] = plan

    sizes = dict(mesh.shape)
    # keep-leaves stay OUT of the compiled program: one already placed
    # in its dst layout passes through untouched; one not yet on the
    # mesh (host array, single-device result) is a plain placement.
    # Only leaves whose layout actually changes ride the shard_map —
    # smaller programs, no identity arguments.
    sched_idx, place_idx = [], []
    for i, leaf_plan in enumerate(plan.leaves):
        if leaf_plan.steps[0][0] != "keep":
            sched_idx.append(i)
            continue
        sharding = getattr(leaves[i], "sharding", None)
        if not (isinstance(sharding, NamedSharding)
                and sharding.mesh == mesh):
            place_idx.append(i)

    t0 = time.perf_counter()
    out_leaves = list(leaves)
    if sched_idx:
        # the schedule SET rides the key: the same (specs, shapes) tree
        # can arrive with different keep subsets placed vs scheduled
        key = plan_key + (tuple(sched_idx),)
        with _FN_LOCK:
            fn = _FN_CACHE.get(key)
        if fn is None:
            bodies = [_leaf_body(plan.leaves[i], sizes)
                      for i in sched_idx]

            def run(*args):
                return tuple(body(arg)
                             for body, arg in zip(bodies, args))

            fn = jax.jit(shard_map(
                run, mesh=mesh,
                in_specs=tuple(src_list[i] for i in sched_idx),
                out_specs=tuple(dst_list[i] for i in sched_idx)))
            with _FN_LOCK:
                _FN_CACHE[key] = fn
        # leaves not yet living on the mesh (host arrays, single-device
        # results) are placed into the src layout first — the schedule
        # itself then never leaves the interconnect
        args = []
        for i in sched_idx:
            leaf = leaves[i]
            sharding = getattr(leaf, "sharding", None)
            if not (isinstance(sharding, NamedSharding)
                    and sharding.mesh == mesh):
                leaf = jax.device_put(
                    jnp.asarray(leaf), NamedSharding(mesh, src_list[i]))
            args.append(leaf)
        moved = fn(*args)
        for i, arr in zip(sched_idx, moved):
            out_leaves[i] = arr
    for i in place_idx:
        out_leaves[i] = jax.device_put(
            jnp.asarray(leaves[i]), NamedSharding(mesh, dst_list[i]))
    out = jax.tree.unflatten(treedef, out_leaves)
    jax.block_until_ready(out)
    seconds = time.perf_counter() - t0

    stats = {"bytes": plan.bytes, "seconds": seconds,
             "counts": plan.counts()}
    if registry is None:
        from veles_tpu.observe.metrics import get_metrics_registry
        registry = get_metrics_registry()
    registry.incr("veles_reshard_bytes_total", plan.bytes,
                  labels={"transition": label},
                  help="interconnect bytes moved by reshard() schedules")
    registry.observe("veles_reshard_seconds", seconds,
                     labels={"transition": label},
                     buckets=RESHARD_BUCKETS,
                     help="wall seconds per reshard() transition")
    return out, stats

