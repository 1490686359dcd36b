"""FusedTick: the product-path tick compiler.

SURVEY §7.1's headline design translation, wired into the REAL workflow
loop: the reference executes one trip around the Repeater loop as a chain
of per-unit kernel launches (loader gather → forward ops → evaluator →
per-layer GD updates, reference ``workflow.py:347-365``); here the whole
tick is ONE jitted XLA computation, including the minibatch gather from
the device-resident dataset and the normalizer — zero host round trips
per tick, params donated through the step so weights never leave HBM.

``StandardWorkflow`` builds its unit graph as usual (the units remain the
composition API, the weight owners, and the fleet/graph execution path),
then — in standalone mode, when the topology is recognizably a
forward/GD chain — splices a :class:`FusedTick` unit in place of the
compute chain:

    start → repeater → loader → FusedTick → decision → {repeater, end}

The backward math is ``jax.grad`` of the same masked loss the evaluator
computes, which is numerically identical to the hand-chained GD units
(``tests/test_nn.py::test_gd_matches_autodiff`` proves the equivalence;
``tests/test_fused.py`` proves end-to-end weight equality per epoch).

Sharding: with a mesh (pod mode) the tick is ``shard_map``-ped over the
``data`` axis — each device gathers its own index shard from the
replicated originals, gradients/metrics are merged over ICI by the
mapreduce primitives (``parallel/mapreduce.py``: ``reduce_sum`` at the
configured ``root.common.fleet.reduce`` tier, f32 == the plain psum) —
the synchronous SPMD answer to the reference's master/slave update
merge. Tensor parallelism for dense chains stays in ``parallel.step``.

Control-plane fleet mode (``root.common.fleet.plane = "control"``,
``docs/compiler_fleet.md``): a SLAVE's tick keeps its params
device-resident across jobs (no per-job refresh from the unit Arrays —
the wire no longer carries weights), stashes a one-slot rollback before
every train tick so a re-issued job (lost update) replays from exactly
the pre-job state, and writes the unit Arrays only at epoch fences
(feeding the fence-sync payload the client ships to the master).
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from veles_tpu.core.units import Unit
from veles_tpu.observe.tracing import get_tracer
from veles_tpu.observe.xla_stats import instrument
from veles_tpu.parallel import mapreduce
from veles_tpu.parallel.mesh import shard_map
from veles_tpu.loader.base import TRAIN, VALID
from veles_tpu.ops import activations as act_lib, losses
from veles_tpu.ops.gather import gather_minibatch
from veles_tpu.loader.normalization import normalizer_registry

#: forward-unit class name → fused layer kind
_DENSE = "dense"
_CONV = "conv"
_ATTN = "attention"
_FFN = "ffn"
_NORM = "layer_norm"
_POOL_KINDS = {"MaxPooling": "max", "AvgPooling": "avg",
               "MaxAbsPooling": "maxabs"}


#: per-leaf update policy: (leaf key, forward attr, gd velocity attr,
#: uses learning_rate_bias, gets l2/l1 decay) — encodes each graph-mode
#: GD unit's exact update math so fused results match bit-for-bit logic
_WB_LEAVES = (("w", "weights", "_velocity_w", False, True),
              ("b", "bias", "_velocity_b", True, False))
_ATTN_LEAVES = (("w", "weights", "_velocity_w", False, True),
                ("b", "bias", "_velocity_b", True, True),
                ("ow", "out_weights", "_velocity_ow", False, True),
                ("ob", "out_bias", "_velocity_ob", True, True))


def extract_model_spec(workflow):
    """Static per-layer config from the workflow's forwards/gds chains.
    Returns a spec list, or None when a layer type is not fusible (the
    caller then stays on graph mode)."""
    from veles_tpu.nn.all2all import All2All, All2AllSoftmax
    from veles_tpu.nn.attention import (GDLayerNorm, GDSelfAttention,
                                        GDTokenFFN, LayerNorm,
                                        SelfAttention, TokenFFN)
    from veles_tpu.nn.conv import Conv, GDConv
    from veles_tpu.nn.gd import GradientDescent
    from veles_tpu.nn.pooling import GDPooling, Pooling

    known_computes = {getattr(cls, "compute", None) for cls in (
        All2All, All2AllSoftmax, Conv, SelfAttention, TokenFFN,
        LayerNorm, Pooling, GradientDescent, GDConv, GDSelfAttention,
        GDTokenFFN, GDLayerNorm, GDPooling)}

    def modified(unit):
        """A subclass that overrides compute() carries custom math the
        spec tables cannot express — fusing it by isinstance would
        silently run the BASE math (the spec is built from class
        attributes, not the override). Such chains belong to the
        sweep/segment tiers, which compose the units' own computes."""
        return (unit is not None
                and getattr(type(unit), "compute", None)
                not in known_computes)

    specs = []
    for i, fwd in enumerate(workflow.forwards):
        gd = workflow.gds[i] if workflow.gds else None
        if modified(fwd) or modified(gd):
            return None
        if isinstance(fwd, All2All):
            spec = {"kind": _DENSE, "activation": fwd.ACTIVATION,
                    "leaves": _WB_LEAVES}
        elif isinstance(fwd, Conv):
            spec = {"kind": _CONV, "activation": fwd.ACTIVATION,
                    "sliding": fwd.sliding, "padding": fwd.padding,
                    "leaves": _WB_LEAVES}
        elif isinstance(fwd, SelfAttention):
            spec = {"kind": _ATTN, "heads": fwd.heads,
                    "causal": fwd.causal,
                    "residual": getattr(fwd, "residual", False),
                    "leaves": _ATTN_LEAVES}
        elif isinstance(fwd, TokenFFN):
            spec = {"kind": _FFN, "activation": fwd.activation,
                    "residual": fwd.residual, "leaves": _ATTN_LEAVES}
        elif isinstance(fwd, LayerNorm):
            spec = {"kind": _NORM, "eps": fwd.eps, "leaves": _WB_LEAVES}
        elif isinstance(fwd, Pooling):
            spec = {"kind": _POOL_KINDS.get(type(fwd).__name__),
                    "window": (fwd.ky, fwd.kx), "sliding": fwd.sliding}
            if spec["kind"] is None:
                return None
        else:
            return None
        if "leaves" in spec:
            if gd is None or not hasattr(gd, "learning_rate"):
                return None
            spec["has_params"] = True
            # per-layer solver (momentum/adam/adagrad) — the fused update must
            # run each GD unit's exact math (gd.py make_updater)
            spec["solver"] = getattr(gd, "solver", "momentum")
        specs.append(spec)
    return specs


def get_hypers(workflow):
    """Per-layer hyperparameter vectors, read fresh from the GD units'
    ``_hyper`` slots each tick — so ``set_learning_rate()`` annealing keeps
    working in fused mode without retracing (the gd.py contract)."""
    return [gd._hyper.data if getattr(fwd, "weights", None) is not None
            else None
            for fwd, gd in zip(workflow.forwards, workflow.gds)]


def get_params(workflow, specs):
    """Snapshot the unit chain's weights into the per-layer pytree:
    ``{"p": {leaf: tensor}, "v": {leaf: velocity}}`` per layer (plus
    ``"s"`` second moments + ``"t"`` step count for stateful solvers), leaves
    named by each spec's update-policy table."""
    params = []
    for fwd, gd, spec in zip(workflow.forwards, workflow.gds, specs):
        if not spec.get("has_params"):
            params.append({})
            continue
        p, v = {}, {}
        entry = {"p": p, "v": v}
        stateful = spec.get("solver", "momentum") != "momentum"
        if stateful:
            entry["s"] = {}
            step = gd._step.data
            entry["t"] = (step if step is not None
                          else jnp.zeros((), jnp.float32))
        for leaf, fwd_attr, vel_attr, _, _ in spec["leaves"]:
            p[leaf] = getattr(fwd, fwd_attr).data
            vel = getattr(gd, vel_attr).data
            v[leaf] = vel if vel is not None else jnp.zeros_like(p[leaf])
            if stateful:
                sec = getattr(gd,
                              vel_attr.replace("_velocity",
                                               "_second")).data
                entry["s"][leaf] = (sec if sec is not None
                                    else jnp.zeros_like(p[leaf]))
        params.append(entry)
    return params


def set_params(workflow, params, specs):
    """Write fused-step results back into the shared unit Array slots (so
    the Snapshotter, exporters, and graph mode all see current weights).

    COPIES, not aliases: the train step donates its params argument, so an
    alias stored in a unit Array would be a deleted buffer one tick later
    (and the Snapshotter may read it concurrently from a pool thread)."""
    for fwd, gd, p, spec in zip(workflow.forwards, workflow.gds, params,
                                specs):
        if not p:
            continue
        stateful = spec.get("solver", "momentum") != "momentum"
        for leaf, fwd_attr, vel_attr, _, _ in spec["leaves"]:
            getattr(fwd, fwd_attr).data = jnp.copy(p["p"][leaf])
            getattr(gd, vel_attr).data = jnp.copy(p["v"][leaf])
            if stateful:
                getattr(gd, vel_attr.replace("_velocity", "_second")
                        ).data = jnp.copy(p["s"][leaf])
        if stateful:
            gd._step.data = jnp.copy(p["t"])


def _layer_forward(spec):
    """Pure forward for one layer, matching the forward unit's compute."""
    kind = spec["kind"]
    if kind == _DENSE:
        from veles_tpu.ops.gemm import dense_layer
        activation = spec["activation"]

        def fwd(p, x):
            x = x.reshape(x.shape[0], -1)
            # XLA's dot with its own bias + activation epilogue fusion
            # — see ops/gemm.py
            return dense_layer(x, p["w"], p["b"], activation=activation,
                               out_dtype=jnp.float32)
        return fwd
    if kind == _CONV:
        from veles_tpu.ops.gemm import conv2d
        act = act_lib.ACTIVATIONS[spec["activation"]][0]
        sliding, padding = spec["sliding"], spec["padding"]

        def fwd(p, x):
            # same precision-policy conv as the graph unit (bit-identical
            # by construction — one shared implementation)
            return act(conv2d(x, p["w"], sliding, padding) + p["b"])
        return fwd
    if kind == _ATTN:
        from veles_tpu.ops.attention import attention_block
        heads, causal = spec["heads"], spec["causal"]
        residual = spec.get("residual", False)

        def fwd(p, x):
            # THE SAME implementation the graph unit runs
            # (nn.attention.SelfAttention._forward delegates there too)
            return attention_block(x, p["w"], p["b"], p["ow"], p["ob"],
                                   heads, causal, residual)
        return fwd
    if kind == _FFN:
        from veles_tpu.ops.attention import ffn_block
        activation = spec["activation"]
        residual = spec.get("residual", True)

        def fwd(p, x):
            # mirrors nn.attention.TokenFFN._forward exactly
            return ffn_block(x, p["w"], p["b"], p["ow"], p["ob"],
                             activation, residual)
        return fwd
    if kind == _NORM:
        eps = spec["eps"]

        def fwd(p, x):
            # mirrors nn.attention.LayerNorm._forward exactly
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            return (x - mean) * lax.rsqrt(var + eps) * p["w"] + p["b"]
        return fwd
    # pooling (mirrors nn.pooling semantics exactly)
    ky, kx = spec["window"]
    window = (1, ky, kx, 1)
    strides = (1,) + tuple(spec["sliding"]) + (1,)
    if kind == "max":
        return lambda p, x: lax.reduce_window(
            x, -jnp.inf, lax.max, window, strides, "VALID")
    if kind == "avg":
        return lambda p, x: lax.reduce_window(
            x, 0.0, lax.add, window, strides, "VALID") / (kx * ky)

    def maxabs(p, x):
        # signed value of the max-|x| element, built from the two
        # DIFFERENTIABLE reduce_windows (a custom absmax reducer has no
        # reverse-mode rule — the train step must grad through pooling)
        mx = lax.reduce_window(x, -jnp.inf, lax.max, window, strides,
                               "VALID")
        mn = lax.reduce_window(x, jnp.inf, lax.min, window, strides,
                               "VALID")
        return jnp.where(jnp.abs(mx) >= jnp.abs(mn), mx, mn)
    return maxabs


def _freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    return obj


#: (frozen specs, norm_type, mesh id) → compiled step tuple. Rebuilding a
#: workflow with the same topology reuses the SAME jitted callables, so
#: jax's in-process trace cache (and the persistent XLA cache) hit.
_TICK_CACHE = {}


def _tick_key(specs, norm_type, with_confusion, augment, loss_kind,
              grad_reduce, mesh):
    """The tick cache key: topology + every engine knob the trace
    folds in. ONE copy — :func:`build_tick` and the AOT adoption seam
    (:func:`install_tick_steps`) must agree on it exactly, or a loaded
    artifact would silently shadow (or miss) the live programs."""
    from veles_tpu.core.config import root
    return (_freeze(specs), norm_type, with_confusion, augment,
            loss_kind, grad_reduce, None if mesh is None else id(mesh),
            root.common.engine.get("precision_level", 0),
            str(root.common.engine.get("compute_dtype", "bfloat16")))


def install_tick_steps(steps, specs, norm_type="none", mesh=None,
                       with_confusion=True, augment="none",
                       loss_kind="softmax", grad_reduce="f32"):
    """Seed the tick cache for this topology with caller-provided step
    callables — the seam the AOT loader (``veles_tpu/aot/loader.py``)
    slots loaded compiled programs into: a later :func:`build_tick`
    with the same key returns THESE steps, so ``FusedTick`` (and the
    fleet wrappers above it) run artifact programs unchanged. Returns
    the previous cache entry (None when the tick was never built)."""
    key = _tick_key(specs, norm_type, with_confusion, augment,
                    loss_kind, grad_reduce, mesh)
    previous = _TICK_CACHE.get(key)
    _TICK_CACHE[key] = tuple(steps)
    return previous


def build_tick(specs, norm_type="none", mesh=None,
               with_confusion=True, augment="none",
               loss_kind="softmax", grad_reduce="f32"):
    """Compile the fused engine.

    Returns ``(train_step, eval_step, train_sweep, eval_sweep)``:

    - ``train_step(params, hypers, norm, data, labels, indices, valid,
      seed) -> (params, (loss, n_err))`` — one minibatch: gather →
      normalize → [augment] → forward → masked softmax xent → grad →
      per-layer momentum/decay update. ``hypers`` (per-layer 5-vectors
      from :func:`get_hypers`) and ``norm`` (normalizer-state dict) are
      traced inputs so annealing and dataset changes never retrace;
      ``augment="mirror"`` applies the loader's in-jit random-mirror
      transform to TRAIN batches, keyed by the loader-drawn ``seed`` —
      the exact math of ``FullBatchImageLoader._augment_jit``, so fused
      and graph mode stay numerically identical;
    - ``eval_step(params, norm, data, labels, indices, valid) ->
      (loss, n_err)`` — forward + metrics only (VALID/TEST sweeps, GD
      skipped exactly as the Decision unit's ``gd_skipped`` gate does in
      graph mode);
    - ``train_sweep(params, hypers, norm, data, labels, index_matrix,
      valid_sizes, total_valid) -> (params, (loss, n_err))`` — a whole
      class sweep as ONE dispatch: ``lax.scan`` over the minibatch rows
      (identical per-row math), metrics summed over the sweep. This is
      what makes the product path dispatch-bound-free: one XLA call per
      class per epoch instead of one per minibatch;
    - ``eval_sweep(...)`` likewise without updates.

    ``grad_reduce`` selects the mesh gradient-merge wire tier
    (``parallel/mapreduce.py``): ``"f32"`` (default, == the plain
    psum), ``"bf16"``, or ``"int8"`` (quantized all-reduce with
    per-leaf scales). Metric scalars always reduce exact. Callers
    building for a mesh normally go through
    ``mapreduce.fleet_train_step``, which also instruments the
    programs for the /metrics plane.
    """
    key = _tick_key(specs, norm_type, with_confusion, augment,
                    loss_kind, grad_reduce, mesh)
    cached = _TICK_CACHE.get(key)
    if cached is not None:
        return cached
    layer_fwds = [_layer_forward(s) for s in specs]
    data_ax = mesh.shape.get("data", 1) if mesh is not None else 1
    with_confusion = with_confusion and loss_kind == "softmax"

    # normalizer coefficients ride in through the traced ``norm`` dict
    # (``jit_state()``), so re-analyzed datasets never retrace the tick
    norm_cls = normalizer_registry[norm_type]

    def gather_norm(data, labels, indices, norm):
        batch, lab = gather_minibatch(data, indices, labels)
        return norm_cls.apply_state(jnp, batch, norm), lab

    def apply_augment(batch, seed):
        # the SAME traced functions the graph path jits — numeric
        # parity with the loaders' fill_minibatch is structural
        from veles_tpu.ops.augment import TRANSFORMS
        transform = TRANSFORMS.get(augment)
        if transform is None:
            return batch
        return transform(batch, seed)

    # the named scopes below are HLO metadata only: they cost nothing
    # at run time and reach a number through the scope table
    # (observe/xla_stats.scope_table), which tells a traced op's
    # instruction back to ``data`` / ``fwd`` / ``update`` / ``reduce``
    # and the layer inside. Backward needs none of its own: JAX writes
    # ``transpose(jvp(fwd))`` where an op is ``fwd``'s gradient
    layer_scopes = ["l%d_%s" % (i, spec["kind"])
                    for i, spec in enumerate(specs)]

    def model_forward(wb, x):
        for fwd, p, scope in zip(layer_fwds, wb, layer_scopes):
            with jax.named_scope(scope):
                x = fwd(p, x)
        return x

    def local_mask(n_local, valid):
        pos = jnp.arange(n_local)
        if data_ax > 1:
            pos = pos + lax.axis_index("data") * n_local
        return (pos < valid).astype(jnp.float32)

    def metrics_of(wb, batch, lab, mask, valid):
        """``lab`` is int labels (softmax) or float targets (mse) — both
        gathered from the device-resident originals by the same indices."""
        with jax.named_scope("fwd"):
            logits = model_forward(wb, batch)
            if loss_kind == "mse":
                _, loss_sum, _ = losses.masked_mse(logits, lab, mask,
                                                   valid)
                return loss_sum, jnp.int32(0), logits
            _, loss_sum, n_err, _ = losses.masked_softmax_xent(
                logits, lab, mask, valid)
            return loss_sum, n_err, logits

    # cores return the UNNORMALIZED loss_sum; wrappers divide by the
    # relevant valid count (per minibatch or per sweep)
    def core_train(params, hypers, norm, data, labels, indices, valid,
                   seed):
        with jax.named_scope("data"):
            batch, lab = gather_norm(data, labels, indices, norm)
            batch = apply_augment(batch, seed)
            mask = local_mask(indices.shape[0], valid)
        wb = [p["p"] if p else {} for p in params]

        def loss_fn(wb):
            loss_sum, n_err, _ = metrics_of(wb, batch, lab, mask, valid)
            return loss_sum / valid, (loss_sum, n_err)

        (_, (loss_sum, n_err)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(wb)
        if data_ax > 1:
            # the in-program fleet aggregation (parallel/mapreduce.py):
            # gradients merge at the configured wire tier (f32 IS the
            # plain psum, bit-identical to the pre-tier programs);
            # metric scalars always reduce exact
            with jax.named_scope("reduce"):
                grads = mapreduce.reduce_sum(grads, "data",
                                             precision=grad_reduce)
                loss_sum = mapreduce.reduce_sum(loss_sum, "data")
                n_err = mapreduce.reduce_sum(n_err, "data")
        new = []
        for p, g, hyper, spec, scope in zip(params, grads, hypers,
                                            specs, layer_scopes):
            if not p:
                new.append({})
                continue
            from veles_tpu.nn.gd import make_updater
            with jax.named_scope("update"), jax.named_scope(scope):
                lr, lr_b, l2, l1 = hyper[0], hyper[1], hyper[2], hyper[3]
                solver = spec.get("solver", "momentum")
                step = p["t"] + 1.0 if solver != "momentum" else None
                upd = make_updater(solver, hyper, step)
                entry = {"p": {}, "v": {}}
                if solver != "momentum":
                    entry["s"], entry["t"] = {}, step
                # per-leaf policy from the spec table: which rate
                # applies and whether l2/l1 decay does — matching each
                # graph-mode GD unit's exact update math (same
                # make_updater)
                for leaf, _, _, use_lr_b, decay in spec["leaves"]:
                    w, gw, vel = p["p"][leaf], g[leaf], p["v"][leaf]
                    if decay:
                        gw = gw + l2 * w + l1 * jnp.sign(w)
                    w2, v2, s2 = upd(w, gw, vel,
                                     p["s"][leaf] if solver != "momentum"
                                     else None,
                                     lr_b if use_lr_b else lr)
                    entry["p"][leaf] = w2
                    entry["v"][leaf] = v2
                    if solver != "momentum":
                        entry["s"][leaf] = s2
            new.append(entry)
        return new, (loss_sum, n_err)

    def core_eval(params, norm, data, labels, indices, valid):
        """Eval additionally emits the confusion-matrix increment (when
        the evaluator asked for it), so the MatrixPlotter / Decision
        accumulation work in fused mode too."""
        with jax.named_scope("data"):
            batch, lab = gather_norm(data, labels, indices, norm)
            mask = local_mask(indices.shape[0], valid)
        wb = [p["p"] if p else {} for p in params]
        loss_sum, n_err, logits = metrics_of(wb, batch, lab, mask, valid)
        with jax.named_scope("fwd"):
            cm = (losses.confusion_matrix(logits, lab, logits.shape[-1],
                                          mask)
                  if with_confusion else jnp.zeros((1, 1), jnp.int32))
        if data_ax > 1:
            with jax.named_scope("reduce"):
                loss_sum = mapreduce.reduce_sum(loss_sum, "data")
                n_err = mapreduce.reduce_sum(n_err, "data")
                cm = mapreduce.reduce_sum(cm, "data")
        return loss_sum, n_err, cm

    def local_train(params, hypers, norm, data, labels, indices, valid,
                    seed):
        new, (loss_sum, n_err) = core_train(params, hypers, norm, data,
                                            labels, indices, valid, seed)
        return new, (loss_sum / valid, n_err)

    def local_eval(params, norm, data, labels, indices, valid):
        loss_sum, n_err, cm = core_eval(params, norm, data, labels,
                                        indices, valid)
        return loss_sum / valid, n_err, cm

    def local_train_sweep(params, hypers, norm, data, labels,
                          index_matrix, valid_sizes, total_valid,
                          seeds):
        def body(carry, xs):
            indices, valid, seed = xs
            new, (loss_sum, n_err) = core_train(
                carry, hypers, norm, data, labels, indices,
                valid.astype(jnp.float32), seed)
            return new, (loss_sum, n_err)

        params, (loss_sums, n_errs) = lax.scan(
            body, params, (index_matrix, valid_sizes, seeds))
        return params, (jnp.sum(loss_sums) / total_valid,
                        jnp.sum(n_errs))

    def local_eval_sweep(params, norm, data, labels, index_matrix,
                         valid_sizes, total_valid):
        def body(carry, xs):
            indices, valid = xs
            return carry, core_eval(params, norm, data, labels, indices,
                                    valid.astype(jnp.float32))

        _, (loss_sums, n_errs, cms) = lax.scan(
            body, 0, (index_matrix, valid_sizes))
        return (jnp.sum(loss_sums) / total_valid, jnp.sum(n_errs),
                jnp.sum(cms, axis=0))

    if data_ax == 1:
        # instrumented like every other hot program: compiles and hits
        # on /metrics, and noted for the scope table while a traced
        # window is open (observe/xla_stats.py). The meshed tick gets
        # the same through mapreduce.fleet_train_step
        steps = (instrument("fused.train_step",
                            jax.jit(local_train, donate_argnums=(0,))),
                 instrument("fused.eval_step", jax.jit(local_eval)),
                 instrument("fused.train_sweep",
                            jax.jit(local_train_sweep,
                                    donate_argnums=(0,))),
                 instrument("fused.eval_sweep",
                            jax.jit(local_eval_sweep)))
        _TICK_CACHE[key] = steps
        return steps
    eval_specs = (P(), P(), P(), P(), P("data"), P())
    train_specs = (P(),) + eval_specs + (P(),)  # + seed
    eval_sweep_specs = (P(), P(), P(), P(), P(None, "data"), P(), P())
    train_sweep_specs = (P(),) + eval_sweep_specs + (P(),)  # + seeds
    train = shard_map(local_train, mesh=mesh, in_specs=train_specs,
                      out_specs=(P(), (P(), P())))
    evaluate = shard_map(local_eval, mesh=mesh, in_specs=eval_specs,
                         out_specs=(P(), P(), P()))
    train_sweep = shard_map(
        local_train_sweep, mesh=mesh, in_specs=train_sweep_specs,
        out_specs=(P(), (P(), P())))
    eval_sweep = shard_map(
        local_eval_sweep, mesh=mesh, in_specs=eval_sweep_specs,
        out_specs=(P(), P(), P()))
    steps = (jax.jit(train, donate_argnums=(0,)), jax.jit(evaluate),
             jax.jit(train_sweep, donate_argnums=(0,)),
             jax.jit(eval_sweep))
    _TICK_CACHE[key] = steps
    return steps


def supports(workflow, mesh=None):
    """True when the workflow's compute chain can run as a fused tick."""
    from veles_tpu.loader.fullbatch import (FullBatchLoader,
                                            FullBatchLoaderMSE)
    from veles_tpu.nn.evaluator import EvaluatorMSE, EvaluatorSoftmax

    loader = getattr(workflow, "loader", None)
    if not isinstance(loader, FullBatchLoader) or not loader.on_device:
        return False
    evaluator = getattr(workflow, "evaluator", None)
    if isinstance(evaluator, EvaluatorMSE):
        # regression tick: targets gathered from the device-resident
        # original_targets exactly like labels
        if not isinstance(loader, FullBatchLoaderMSE):
            return False
    elif not isinstance(evaluator, EvaluatorSoftmax):
        return False
    if getattr(loader, "has_fill_transforms", False):
        # the fused gather bypasses fill_minibatch — fusion stays on
        # only for transforms the tick replicates in-jit itself
        # (single-device: per-sample randomness draws over the GLOBAL
        # minibatch, which a data-sharded tick could not reproduce)
        from veles_tpu.ops.augment import TRANSFORMS
        if getattr(loader, "jit_transform", None) not in TRANSFORMS \
                or mesh is not None:
            return False
    if extract_model_spec(workflow) is None:
        return False
    # the control chain must be EXACTLY the standard topology: a custom
    # unit spliced into the cycle (it wouldn't appear in .forwards/.gds)
    # must not be silently dropped by the fused splice — such chains
    # belong to the partial-fusion tier (parallel/segments.py)
    from veles_tpu.parallel.segments import chain_of
    chain = chain_of(workflow)
    expected = (list(workflow.forwards) + [workflow.evaluator,
                                           workflow.decision]
                + list(reversed(workflow.gds)))
    if chain != expected:
        return False
    if mesh is not None:
        data_ax = mesh.shape.get("data", 1)
        if loader.max_minibatch_size % data_ax:
            return False
    return True


class FusedTick(Unit):
    """One workflow tick as one fused XLA computation.

    Reads the loader's served indices + epoch flags, runs the train or
    eval step for the tick's sample class, writes the metric scalars into
    the evaluator's slots (lazy device values — the Decision unit reads
    them at epoch boundaries exactly as in graph mode), and writes weights
    back into the unit Arrays at epoch boundaries so the Snapshotter and
    fleet paths always see current state.
    """

    hide_from_registry = True
    VIEW_GROUP = "WORKER"
    #: execution strategy, not topology: excluded from the workflow
    #: checksum so fused slaves pair with graph masters
    EPHEMERAL = True

    def __init__(self, workflow, mesh=None, pipelined=False, **kwargs):
        super().__init__(workflow, **kwargs)
        # trailing underscore: a jax Mesh holds Device objects and cannot
        # be pickled — a resumed pod-mode snapshot falls back to the
        # single-device fused tick unless the caller re-supplies a mesh
        self.mesh_ = mesh
        #: pipelined epoch mode: the Decision materializes each epoch's
        #: metrics one epoch late (pipeline_depth=1) so the per-epoch
        #: device sync overlaps the next epoch's compute. The tick then
        #: keeps a one-slot params history so (a) the unit Arrays always
        #: hold the weights the CURRENTLY-ATTRIBUTED metrics scored and
        #: (b) a lagged no-improvement stop can roll back the one
        #: speculatively-trained epoch — outputs stay identical to the
        #: unpipelined run.
        self.pipelined = pipelined
        self.ticks = 0

    @property
    def mesh(self):
        return self.mesh_

    def init_unpickled(self):
        super().init_unpickled()
        if not hasattr(self, "mesh_"):
            self.mesh_ = None
        self._params_ = None
        self._steps_ = None
        self._norm_ = None
        self._specs_ = None
        #: control-plane fleet: params snapshot taken before the last
        #: TRAIN tick — a re-issued job (lost update) rolls back to it
        self._rollback_ = None
        self._wrote_eval_params_ = False
        if not hasattr(self, "pipelined"):
            self.pipelined = False
        self._eval_stash_ = None  # params evaluated one epoch ago
        self._stashed_this_epoch_ = False

    def initialize(self, **kwargs):
        wf = self.workflow
        loader = wf.loader
        if not loader.on_device:
            # the loader's HBM-OOM fallback kicked in during load_data —
            # fused gather from host originals would re-transfer the whole
            # dataset every tick; revert to graph mode
            self.warning("dataset fell back to host: disabling fused mode")
            if wf.is_slave:
                wf._disable_fused_slave()
            else:
                wf._disable_fused()
            return
        if self.mesh_ is not None:
            # a resumed snapshot can acquire a mesh the original build
            # never validated (supports() runs before the splice only)
            data_ax = self.mesh_.shape.get("data", 1)
            if loader.max_minibatch_size % data_ax:
                self.warning(
                    "minibatch size %d does not divide by the mesh data "
                    "axis %d — running the fused tick single-device",
                    loader.max_minibatch_size, data_ax)
                self.mesh_ = None
        for fwd in wf.forwards:
            weights = getattr(fwd, "weights", None)
            if weights is not None and weights.data is None:
                return True  # retry after the forwards initialize
        if self.pipelined:
            if (not getattr(loader, "sweep_serving", False)
                    or loader.effective_class_lengths[VALID] == 0):
                # lagged improvement tracking needs a VALID sweep; and
                # without sweep serving there is no per-epoch sync to
                # hide in the first place
                self.warning("pipelined mode needs sweep serving and a "
                             "validation split: disabling")
                self.pipelined = False
            wf.decision.pipeline_depth = 1 if self.pipelined else 0
        from veles_tpu.nn.evaluator import EvaluatorMSE
        self._loss_kind_ = ("mse" if isinstance(wf.evaluator,
                                                EvaluatorMSE)
                            else "softmax")
        self._specs_ = extract_model_spec(wf)
        self._norm_ = {k: jnp.asarray(v) for k, v in
                       loader.normalizer.jit_state().items()}
        if self.mesh_ is not None:
            # meshed ticks build through the mapreduce layer: same
            # compiled programs (build_tick underneath, f32 reduce ==
            # the old psum) plus xla_stats instrumentation and the
            # configured gradient-reduce wire tier
            self._steps_ = mapreduce.fleet_train_step(
                self.mesh_, self._specs_, loader.normalization_type,
                with_confusion=getattr(wf.evaluator,
                                       "compute_confusion", True),
                augment=getattr(loader, "jit_transform", None)
                or "none",
                loss_kind=self._loss_kind_)
        else:
            self._steps_ = build_tick(
                self._specs_, loader.normalization_type, self.mesh_,
                with_confusion=getattr(wf.evaluator,
                                       "compute_confusion", True),
                augment=getattr(loader, "jit_transform", None)
                or "none",
                loss_kind=self._loss_kind_)

    def run(self):
        import numpy
        wf = self.workflow
        loader = wf.loader
        control = wf.is_slave and self._control_plane()
        if self._params_ is None or (wf.is_slave and not control):
            # copy: the unit Arrays keep their own buffers — ours get
            # donated through the train step. A data-plane SLAVE
            # refreshes every tick: the master overwrites the unit
            # Arrays between jobs (apply_data_from_master). A
            # CONTROL-plane slave keeps its params device-resident —
            # the wire no longer carries weights, so the local replica
            # is the authoritative mid-epoch state
            self._params_ = jax.tree.map(
                jnp.copy, get_params(wf, self._specs_))
        train_step, eval_step, train_sweep, eval_sweep = self._steps_
        norm = self._norm_
        data = loader.original_data.data
        if getattr(self, "_loss_kind_", "softmax") == "mse":
            # regression: the "labels" lane carries the float targets
            labels = loader.original_targets.data
        else:
            labels = loader.labels_for_gather()
        indices = loader.minibatch_indices.data
        valid = numpy.float32(max(loader.minibatch_valid_size, 1))
        training = loader.minibatch_class == TRAIN
        if control:
            # one-slot rollback stash: a job whose update frame is
            # lost gets re-issued by the master; the replay must start
            # from exactly the pre-job params (sync-mode pipelining
            # bounds the unacknowledged depth to one). Eval ticks
            # mutate nothing — no slot, rollback_job is then a no-op
            self._rollback_ = (jax.tree.map(jnp.copy, self._params_)
                               if training else None)
        # one span per dispatch, round the call alone (argument
        # preparation to the jitted call's return): what the host pays
        # to put a program on the device's queue
        tracer = get_tracer()
        if getattr(loader, "sweep_serving", False):
            sizes = loader.sweep_valid_sizes
            if training:
                with tracer.span("engine.train_sweep"):
                    seeds = getattr(loader, "sweep_transform_seeds",
                                    None)
                    if seeds is None:
                        seeds = numpy.zeros(len(sizes), numpy.int64)
                    self._params_, (loss, n_err) = train_sweep(
                        self._params_, get_hypers(wf), norm, data,
                        labels, indices, sizes, valid, seeds)
            else:
                with tracer.span("engine.eval_sweep"):
                    loss, n_err, cm = eval_sweep(
                        self._params_, norm, data, labels, indices,
                        sizes, valid)
        elif training:
            with tracer.span("engine.train_step"):
                seed = numpy.int64(getattr(
                    loader, "minibatch_transform_seed", 0))
                self._params_, (loss, n_err) = train_step(
                    self._params_, get_hypers(wf), norm, data, labels,
                    indices, valid, seed)
        else:
            with tracer.span("engine.eval_step"):
                loss, n_err, cm = eval_step(self._params_, norm, data,
                                            labels, indices, valid)
        evaluator = wf.evaluator
        evaluator.loss.data = loss
        if getattr(evaluator, "n_err", None) is not None:
            evaluator.n_err.data = n_err
        if not training \
                and getattr(self, "_loss_kind_", "softmax") != "mse" \
                and getattr(evaluator, "compute_confusion", True):
            # eval passes also emit the confusion increment, so the
            # Decision accumulation + MatrixPlotter work in fused mode
            evaluator.confusion_matrix.data = cm
        self.ticks += 1
        if wf.is_slave:
            if control:
                # control plane: the unit Arrays are written only at
                # EPOCH FENCES — they feed the bulk fence-sync payload
                # the client ships (docs/compiler_fleet.md); per-job
                # updates carry scalars only
                if bool(loader.epoch_ended):
                    self._write_back(self._params_)
            elif training:
                # data plane (one tick per job): write the trained
                # weights straight back so generate_data_for_master
                # ships them; epoch accounting lives on the master
                self._write_back(self._params_)
            return
        if not training and loader.epoch_ended_for_class:
            # write the EVALUATED weights into the unit Arrays now —
            # they stay untouched through the upcoming train sweep, so a
            # Snapshotter firing on ``improved`` captures exactly the
            # weights that scored the validation metric (the reference's
            # snapshot-on-improved semantics; with the decision's
            # deferred sweep materialization ``improved`` fires on the
            # epoch-end tick, after this epoch's training)
            if self.pipelined:
                # metrics are attributed one epoch late: the Arrays must
                # lag the same way. Rotate the one-slot history — write
                # the params the PREVIOUS epoch evaluated, stash the
                # ones this epoch's eval sweep is scoring right now.
                if not self._stashed_this_epoch_:
                    with tracer.span("engine.write_back"):
                        current = jax.tree.map(jnp.copy, self._params_)
                    if self._eval_stash_ is not None:
                        self._write_back(self._eval_stash_)
                    self._eval_stash_ = current
                    self._stashed_this_epoch_ = True
            else:
                self._write_back(self._params_)
            self._wrote_eval_params_ = True
        if loader.epoch_ended:
            # the eval-tick write stands in for the epoch-end one ONLY
            # when a VALID class exists — improvement then tracks the
            # eval metric. Without VALID samples the Decision tracks
            # THIS epoch's train error, so the Arrays must follow the
            # post-train state (a TEST-only eval write would pin them
            # one epoch behind the tracked metric)
            eval_covers = (getattr(self, "_wrote_eval_params_", False)
                           and loader.effective_class_lengths[VALID] > 0)
            if training and not eval_covers:
                self._write_back(self._params_)
            self._wrote_eval_params_ = False
            self._stashed_this_epoch_ = False

    def _write_back(self, params):
        """Copy ``params`` into the unit Arrays (an eval or epoch
        fence), under a span of its own: one device copy per leaf,
        dispatched from the host between two sweeps."""
        with get_tracer().span("engine.write_back"):
            set_params(self.workflow, params, self._specs_)

    @staticmethod
    def _control_plane():
        from veles_tpu.fleet import fleet_control_plane
        return fleet_control_plane()

    def rollback_job(self):
        """Control-plane fleet: undo the LAST job's local application.
        Returns True when params were actually restored (the last job
        was a train tick); False when there was nothing to undo (eval
        tick — idempotent to re-run). Called by the fleet client when
        the master re-issues work whose update never arrived."""
        if self._rollback_ is None:
            return False
        self._params_ = self._rollback_
        self._rollback_ = None
        return True

    def reset_residency(self):
        """Drop the device-resident params so the next tick refreshes
        from the unit Arrays — called after a master handshake applied
        fresh initial weights (master restart / first join in
        control-plane mode)."""
        self._params_ = None
        self._rollback_ = None

    def advance_eval_params(self):
        """Write the one-slot history's evaluated params into the unit
        Arrays — the Decision calls this when a multi-epoch drain is
        about to attribute an improvement to the NEWER epoch, whose
        evaluated weights sit in the stash (see _drain_epochs)."""
        if self._eval_stash_ is not None:
            set_params(self.workflow, self._eval_stash_, self._specs_)
            self._eval_stash_ = None

    def rollback_speculative(self):
        """A lagged stop decision arrived AFTER one more epoch was
        speculatively dispatched: restore the params to the stopping
        epoch's post-train state (the one-slot stash holds exactly it —
        pipeline depth is 1)."""
        if self._eval_stash_ is not None:
            self._params_ = self._eval_stash_
            self._eval_stash_ = None

    def sync_params(self):
        """Write the CURRENT (post-train) params into the unit Arrays —
        called when the workflow finishes so exports, results and the
        final snapshot see the last training state."""
        if self._params_ is not None and self._specs_ is not None:
            set_params(self.workflow, self._params_, self._specs_)
