"""The tick compiler: one fused SPMD train step for a unit-chain workflow.

SURVEY §7's central design translation: the reference executes a tick as a
chain of per-unit kernel launches (loader gather → forward GEMMs →
evaluator → per-layer GD updates); here the whole tick is traced into ONE
jitted, mesh-sharded computation. The unit graph remains the composition
API — this module *extracts* the static spec (layer activations,
hyperparameters, normalization) from the live units and emits the fused
function, so graph-mode and fused-mode are numerically identical.

Shardings (over ``veles_tpu.parallel.mesh`` axes):

- **data**: batch rows; gradients are ``psum``-merged over ICI — the
  synchronous TPU answer to the reference's master/slave update merge;
- **model**: Megatron-style column sharding of every layer's weights;
  activations ``all_gather``-ed between layers, weight-gradient slices
  computed locally, input-error partial sums ``psum``-ed.

Params/state live as a pytree ``{"w": [...], "b": [...], "vw": [...],
"vb": [...]}`` donated through the step, so weights stay device-resident
across the epoch with zero host traffic.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.ops import activations as act_lib, losses
from veles_tpu.parallel.mesh import shard_map
from veles_tpu.ops.gemm import matmul


def extract_layer_spec(workflow):
    """Static per-layer config from a workflow's forwards/gds chains."""
    spec = []
    for i, fwd in enumerate(workflow.forwards):
        gd = workflow.gds[i] if workflow.gds else None
        spec.append({
            "activation": fwd.ACTIVATION,
            "learning_rate": gd.learning_rate if gd else 0.0,
            "learning_rate_bias": (
                gd.learning_rate_bias if gd and gd.learning_rate_bias
                is not None else (gd.learning_rate if gd else 0.0)),
            "weights_decay": gd.weights_decay if gd else 0.0,
            "l1_vs_l2": gd.l1_vs_l2 if gd else 0.0,
            "gradient_moment": gd.gradient_moment if gd else 0.0,
        })
    return spec


def get_params(workflow):
    """Snapshot the unit chain's weights into the fused-step pytree."""
    return {
        "w": [fwd.weights.data for fwd in workflow.forwards],
        "b": [fwd.bias.data for fwd in workflow.forwards],
        "vw": [gd._velocity_w.data if gd._velocity_w.data is not None
               else jnp.zeros_like(fwd.weights.data)
               for gd, fwd in zip(workflow.gds, workflow.forwards)],
        "vb": [gd._velocity_b.data if gd._velocity_b.data is not None
               else jnp.zeros_like(fwd.bias.data)
               for gd, fwd in zip(workflow.gds, workflow.forwards)],
    }


def set_params(workflow, params):
    """Write fused-step results back into the shared unit Array slots."""
    for i, fwd in enumerate(workflow.forwards):
        fwd.weights.data = params["w"][i]
        fwd.bias.data = params["b"][i]
        workflow.gds[i]._velocity_w.data = params["vw"][i]
        workflow.gds[i]._velocity_b.data = params["vb"][i]


def build_train_step(layer_spec, mesh=None, donate=True):
    """Compile the fused train step.

    Returns ``step(params, batch, labels, mask) -> (params, metrics)`` where
    metrics = (loss, n_err). With a mesh, the step is shard_map-ped over
    (data, model) with the collectives described in the module docstring.
    """
    n_layers = len(layer_spec)
    acts = [act_lib.ACTIVATIONS[s["activation"]] for s in layer_spec]
    hyper = [(s["learning_rate"], s["learning_rate_bias"],
              s["weights_decay"], s["l1_vs_l2"], s["gradient_moment"])
             for s in layer_spec]
    data_ax = mesh.shape.get("data", 1) if mesh is not None else 1
    model_ax = mesh.shape.get("model", 1) if mesh is not None else 1

    def local_step(params, batch, labels, mask):
        # ---- forward, saving activations ----
        x = batch.reshape(batch.shape[0], -1)
        saved = [x]
        for i in range(n_layers):
            w, b = params["w"][i], params["b"][i]
            y = matmul(x, w, out_dtype=jnp.float32)
            if model_ax > 1:  # columns sharded: assemble the full width
                y = jax.lax.all_gather(y, "model", axis=1, tiled=True)
            y = y + _full_bias(params["b"][i], model_ax)
            if i < n_layers - 1:
                y = acts[i][0](y)
            saved.append(y)
            x = y
        logits = saved[-1]

        # ---- evaluator: softmax xent on the global batch (shared op —
        # keeps fused mode numerically identical to EvaluatorSoftmax) ----
        valid = jnp.sum(mask)
        if data_ax > 1:
            valid = jax.lax.psum(valid, "data")
        valid = jnp.maximum(valid, 1.0)
        err, loss_sum, n_err, _ = losses.masked_softmax_xent(
            logits, labels, mask, valid)
        if data_ax > 1:
            loss_sum = jax.lax.psum(loss_sum, "data")
            n_err = jax.lax.psum(n_err, "data")
        loss = loss_sum / valid

        # ---- backward + update, deepest layer first ----
        new = {"w": list(params["w"]), "b": list(params["b"]),
               "vw": list(params["vw"]), "vb": list(params["vb"])}
        for i in reversed(range(n_layers)):
            lr, lr_b, l2, l1, moment = hyper[i]
            w, b = params["w"][i], params["b"][i]
            y = saved[i + 1]
            if i < n_layers - 1:
                err = err * acts[i][1](y)
            err_local = _model_shard(err, model_ax)  # this device's columns
            grad_w = matmul(saved[i].T, err_local, out_dtype=jnp.float32)
            grad_b = jnp.sum(err_local, axis=0)
            if data_ax > 1:
                grad_w = jax.lax.psum(grad_w, "data")
                grad_b = jax.lax.psum(grad_b, "data")
            grad_w = grad_w + l2 * w + l1 * jnp.sign(w)
            if i > 0:
                err = matmul(err_local, w.T, out_dtype=jnp.float32)
                if model_ax > 1:  # partial over column shards
                    err = jax.lax.psum(err, "model")
            vw = moment * new["vw"][i] - lr * grad_w
            vb = moment * new["vb"][i] - lr_b * grad_b
            new["w"][i] = w + vw
            new["b"][i] = b + vb
            new["vw"][i] = vw
            new["vb"][i] = vb
        return new, (loss, n_err)

    if mesh is None or (data_ax == 1 and model_ax == 1):
        fused = local_step
        jit_kwargs = {}
        if donate:
            jit_kwargs["donate_argnums"] = (0,)
        return jax.jit(fused, **jit_kwargs)

    wspec = P(None, "model")
    bspec = P("model")
    param_specs = {"w": [wspec] * n_layers, "b": [bspec] * n_layers,
                   "vw": [wspec] * n_layers, "vb": [bspec] * n_layers}
    in_specs = (param_specs, P("data"), P("data"), P("data"))
    out_specs = (param_specs, (P(), P()))
    fused = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs)
    jit_kwargs = {"donate_argnums": (0,)} if donate else {}
    return jax.jit(fused, **jit_kwargs)


def _full_bias(b, model_ax):
    if model_ax > 1:
        return jax.lax.all_gather(b, "model", axis=0, tiled=True)
    return b


def _model_shard(err, model_ax):
    """Slice this device's column block out of a full-width error."""
    if model_ax == 1:
        return err
    cols = err.shape[1] // jax.lax.axis_size("model")
    idx = jax.lax.axis_index("model")
    return jax.lax.dynamic_slice_in_dim(err, idx * cols, cols, axis=1)


def shard_params(params, mesh):
    """Place a params pytree onto the mesh with the step's shardings."""
    wsh = NamedSharding(mesh, P(None, "model"))
    bsh = NamedSharding(mesh, P("model"))
    return {
        "w": [jax.device_put(w, wsh) for w in params["w"]],
        "b": [jax.device_put(b, bsh) for b in params["b"]],
        "vw": [jax.device_put(v, wsh) for v in params["vw"]],
        "vb": [jax.device_put(v, bsh) for v in params["vb"]],
    }


def shard_batch(arrays, mesh):
    """Place (batch, labels, mask) with data-axis sharding."""
    return [jax.device_put(a, NamedSharding(mesh, P("data")))
            for a in arrays]
