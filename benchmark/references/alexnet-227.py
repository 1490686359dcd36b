"""Plain reference for the ``alexnet-227`` configuration.

Straightforward ``jax.numpy`` in float32 with every product at
``Precision.HIGHEST``: no kernels, no fused tick, nothing imported from
``veles_tpu`` and nothing taken from it (the initial values, the
normalisation statistics and the data are made here from the seed).
It follows ``benchmark/configs/alexnet-227.json``: the layer list, the
smooth RELU ``log(1+exp(x))`` after every layer but the last, softmax
cross-entropy averaged over the minibatch, ``mean_disp`` normalisation
(subtract the train set's per-pixel mean, divide by its per-pixel
max-min), and momentum SGD ``v = m*v - lr*(g + wd*w)``, ``w += v``
with weight decay on weights only.

``operands`` rounds the two operands of every convolution and matrix
product (and, through autodiff, the cotangents that flow back through
those roundings) to a lower type before the float32 product:
``"float32"`` is the reference, ``"float8_e4m3fn"`` the control the
configuration names, ``"bfloat16"`` what the configuration states for
the program (a second witness, not the reference).

``half_batch`` plants the fault "half of the batch left out, the mean
taken over the rest" and ``frozen`` the fault "a step returns its state
unchanged" — used only by ``benchmark/tools/calibrate_train.py`` and
the tests.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def layer_shapes(config):
    """[(kind, weight shape or None, output (h, w, c) or (units,))]"""
    h, w, c = config["input_shape"]
    out = []
    for layer in config["layers"]:
        kind = layer["type"]
        if kind == "conv":
            stride = layer["stride"]
            shape = (layer["ky"], layer["kx"], c, layer["kernels"])
            h, w, c = -(-h // stride), -(-w // stride), layer["kernels"]
            out.append((kind, shape, (h, w, c)))
        elif kind == "max_pool":
            k, stride = layer["k"], layer["stride"]
            h, w = (h - k) // stride + 1, (w - k) // stride + 1
            out.append((kind, None, (h, w, c)))
        else:
            fan_in = h * w * c
            shape = (fan_in, layer["units"])
            h, w, c = 1, 1, layer["units"]
            out.append((kind, shape, (layer["units"],)))
    return out


def init_params(seed, config):
    """[{"w", "b"} or {}] per layer, float32 on the default device, in
    one jitted call: U(-1/sqrt(fan_in), 1/sqrt(fan_in)) weights, zero
    biases."""
    shapes = [shape for _, shape, _ in layer_shapes(config)]

    @jax.jit
    def make(key):
        params = []
        for i, shape in enumerate(shapes):
            if shape is None:
                params.append({})
                continue
            bound = 1.0 / math.sqrt(math.prod(shape[:-1]))
            params.append({
                "w": jax.random.uniform(jax.random.fold_in(key, i), shape,
                                        jnp.float32, -bound, bound),
                "b": jnp.zeros((shape[-1],), jnp.float32)})
        return params

    # --seed may need more than 32 signed bits: fold the high part in
    seed = int(seed)
    return make(jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                                   seed >> 31))


def _round(x, operands):
    if operands == "float32":
        return x
    return x.astype(jnp.dtype(operands)).astype(jnp.float32)


def forward(config, params, x, operands="float32"):
    """Normalised pixels (N, H, W, C) -> logits (N, classes)."""
    last = len(config["layers"]) - 1
    for i, (layer, p) in enumerate(zip(config["layers"], params)):
        kind = layer["type"]
        if kind == "max_pool":
            k, s = layer["k"], layer["stride"]
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                                  (1, s, s, 1), "VALID")
            continue
        if kind == "conv":
            s = layer["stride"]
            x = lax.conv_general_dilated(
                _round(x, operands), _round(p["w"], operands), (s, s),
                layer["padding"],
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=HIGHEST) + p["b"]
        else:
            x = jnp.dot(_round(x.reshape(x.shape[0], -1), operands),
                        _round(p["w"], operands),
                        precision=HIGHEST) + p["b"]
        if layer.get("activation", config["activation"]) == "softplus" \
                and i != last:
            x = jax.nn.softplus(x)
    return x


def loss_of(config, params, x, labels, operands="float32"):
    """Mean softmax cross-entropy of one minibatch."""
    logp = jax.nn.log_softmax(forward(config, params, x, operands), -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))


def normaliser(data, n_valid):
    """mean_disp statistics of the TRAIN rows (those after the first
    ``n_valid``): per-pixel mean and max-min (1 where they are equal)."""
    train = data[n_valid:]
    mean = jnp.mean(train, axis=0)
    disp = jnp.max(train, axis=0) - jnp.min(train, axis=0)
    return mean, jnp.where(disp == 0, 1.0, disp)


@functools.partial(jax.jit, static_argnames=("config_key", "operands",
                                             "half_batch", "frozen"))
def _train_sweep(config_key, params, velocity, data, labels, mean, disp,
                 index_matrix, operands, half_batch, frozen):
    config = _CONFIGS[config_key]
    lr = config["learning_rate"]
    moment = config["gradient_moment"]
    decay = config["weights_decay"]

    def step(carry, rows):
        params, velocity = carry
        if half_batch:
            rows = rows[:rows.shape[0] // 2]
        x = (jnp.take(data, rows, axis=0) - mean) / disp
        y = jnp.take(labels, rows, axis=0)
        loss, grads = jax.value_and_grad(
            lambda p: loss_of(config, p, x, y, operands))(params)
        new_p, new_v = [], []
        for p, v, g in zip(params, velocity, grads):
            if not p:
                new_p.append({})
                new_v.append({})
                continue
            vw = moment * v["w"] - lr * (g["w"] + decay * p["w"])
            vb = moment * v["b"] - lr * g["b"]
            new_v.append({"w": vw, "b": vb})
            new_p.append({"w": p["w"] + vw, "b": p["b"] + vb})
        if frozen:
            return (params, velocity), loss
        return (new_p, new_v), loss

    (params, velocity), losses = lax.scan(step, (params, velocity),
                                          index_matrix)
    return params, velocity, losses


@functools.partial(jax.jit, static_argnames=("config_key", "operands"))
def _eval_sweep(config_key, params, data, labels, mean, disp,
                index_matrix, operands):
    config = _CONFIGS[config_key]

    def step(_, rows):
        x = (jnp.take(data, rows, axis=0) - mean) / disp
        y = jnp.take(labels, rows, axis=0)
        return 0, loss_of(config, params, x, y, operands)

    return lax.scan(step, 0, index_matrix)[1]


#: jit wants hashable statics: configurations are registered by their
#: JSON text and looked up inside the traced functions
_CONFIGS = {}


def _key(config):
    import json
    key = json.dumps(config, sort_keys=True)
    _CONFIGS.setdefault(key, config)
    return key


def leaf_norms(tree_a, tree_b=None):
    """Per-leaf Euclidean norms of ``a`` (or of ``a - b``) as a flat
    float32 vector in layer order, w before b."""
    out = []
    for i, layer in enumerate(tree_a):
        for name in ("w", "b"):
            if name in layer:
                leaf = layer[name]
                if tree_b is not None:
                    leaf = leaf - tree_b[i][name]
                out.append(jnp.sqrt(jnp.sum(jnp.square(
                    leaf.astype(jnp.float32)))))
    return jnp.stack(out)


def follow_first_epoch(config, seed, data, labels, train_rows,
                       minibatch, operands="float32", half_batch=False,
                       frozen=False):
    """What the configuration says epoch 0 and the validation sweep of
    epoch 1 produce: validation loss at the initial values, the train
    sweep over ``train_rows`` (the order in which the loader served
    them, reshaped (steps, minibatch)), and the validation loss after
    it. ``data``/``labels`` are device arrays laid out
    [validation | train]. Returns a dict of host floats and per-leaf
    norm lists."""
    n_valid = config["dataset"]["n_valid"]
    key = _key(config)
    mean, disp = jax.jit(normaliser, static_argnums=1)(data, n_valid)
    start = init_params(seed, config)
    valid_rows = jnp.arange(n_valid).reshape(-1, minibatch)
    train_rows = jnp.asarray(train_rows).reshape(-1, minibatch)
    zeros = jax.tree.map(jnp.zeros_like, start)
    valid0 = _eval_sweep(key, start, data, labels, mean, disp,
                         valid_rows, operands)
    params, velocity, losses = _train_sweep(
        key, start, zeros, data, labels, mean, disp, train_rows,
        operands, half_batch, frozen)
    valid1 = _eval_sweep(key, params, data, labels, mean, disp,
                         valid_rows, operands)
    norms = jax.jit(lambda p, v, s: (leaf_norms(p, s), leaf_norms(v)))(
        params, velocity, start)
    return {
        "loss_valid0": float(jnp.mean(valid0)),
        "loss_train0": float(jnp.mean(losses)),
        "loss_valid1": float(jnp.mean(valid1)),
        "dparam_norms": [float(v) for v in norms[0]],
        "velocity_norms": [float(v) for v in norms[1]],
    }
