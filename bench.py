"""Benchmark harness: MNIST784 *workflow-path* training throughput.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

What is measured (this is the path ``python -m veles_tpu`` executes — not
a synthetic kernel loop): the reference MNIST784 topology
(784→100 tanh→10 softmax, minibatch 100) over an MNIST-shaped 60k-sample
dataset, trained end-to-end through ``MLPWorkflow.run()`` with the fused
tick engine (one XLA computation per tick, in-jit gather from the
device-resident dataset — ``veles_tpu/parallel/fused.py``).

``vs_baseline`` is the speedup of that fused product path over the SAME
workflow executed in graph mode (per-unit jit dispatch — the faithful
translation of the reference's per-kernel-launch hot loop,
``veles/workflow.py:347-365``). Extra keys report the graph-mode
absolute, and the raw fused-step GFLOP/s of a 784→4096→10 MLP against
the reference's GTX-TITAN GEMM anchor (0.1642 s per 3001² matmul,
``devices/device_infos.json:2-27``) for GPU-era context.
"""

import json
import math
import os
import time

import numpy

import jax
import jax.numpy as jnp

# MFU is reported against the bf16 peak — the MXU's native precision;
# our steps feed fp32 inputs with DEFAULT precision (XLA runs them
# through bf16-based passes), so bf16 peak is the honest ceiling. ONE
# table serves the bench and the online veles_mfu_ratio gauge.
from veles_tpu.observe import xla_stats
from veles_tpu.ops import platform


def device_info():
    """(device_kind, peak_bf16_tflops) of the bench device: the peak
    is the exact-match row of ``xla_stats.PEAK_BF16_TFLOPS`` (an
    unlisted TPU kind raises there), None off the TPU."""
    return jax.devices()[0].device_kind, xla_stats.peak_tflops()


def _mfu(gflops, peak_tflops):
    if not gflops or not peak_tflops:
        return None
    return round(gflops / (peak_tflops * 1000.0), 4)


def _mean_std(values):
    mean = sum(values) / len(values)
    var = sum((v - mean) ** 2 for v in values) / len(values)
    return mean, var ** 0.5


def _balanced_labels(rng, classes, *split_lengths):
    """Concatenated label blocks, each as class-balanced as ``length``
    allows and shuffled — EXACTLY proportional splits keep the
    loader's imbalance + chi-square checks quiet (random labels
    tripped them; expected==observed gives p=1.0).
    ONE copy for every bench dataset."""
    blocks = []
    for length in split_lengths:
        block = numpy.tile(numpy.arange(classes, dtype=numpy.int32),
                           length // classes + 1)[:length]
        rng.shuffle(block)
        blocks.append(block)
    return numpy.concatenate(blocks)


def _dataset(n=60000, features=784, classes=10, n_valid=10000):
    """MNIST-shaped synthetic set with balanced, proportional splits."""
    rng = numpy.random.RandomState(0)
    data = rng.rand(n, features).astype(numpy.float32)
    labels = _balanced_labels(rng, classes, n_valid, n - n_valid)
    return data, labels


def _build(fused, data, labels, epochs):
    from veles_tpu.core import prng
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.mlp import MLPWorkflow

    prng.get("default").seed(1234)
    prng.get("loader").seed(1234)
    return MLPWorkflow(
        DummyLauncher(), layers=(100, 10),
        loader_kwargs=dict(data=data, labels=labels,
                           class_lengths=[0, 10000, 50000],
                           minibatch_size=100,
                           normalization_type="linear"),
        learning_rate=0.03, max_epochs=epochs, fused=fused,
        name="bench784")


def workflow_throughput(fused, data, labels, epochs=3):
    """Steady-state images/sec through the real Workflow.run() loop.

    Timed between the first and last epoch boundary of one run, so the
    one-time costs (XLA compile, dataset upload to the device) sit in
    epoch 1 and the measured epochs are what a long training run sees.

    Fused (pipelined) path: the MEAN over the measured epochs — the
    host enqueues ahead of the device, so a single epoch interval can
    undershoot the device-bound sustained rate; the final epoch's
    materialization waits for all queued compute, making the mean
    honest. Graph mode keeps the fastest interval (every tick syncs, so
    intervals only vary with dispatch noise)."""
    n_epochs = (epochs + 4) if fused else epochs  # amortize the drain
    wf = _build(fused, data, labels, n_epochs + 1)
    wf.initialize()
    times = []
    inner = wf.decision._on_epoch_ended

    def stamped():
        times.append(time.perf_counter())
        inner()

    wf.decision._on_epoch_ended = stamped
    wf.run()
    deltas = [b - a for a, b in zip(times, times[1:])]
    dt = sum(deltas) / len(deltas) if fused else min(deltas)
    return len(data) / dt, deltas


def _epoch_rate(wf, n):
    """Mean-epoch-interval images/sec through one ``Workflow.run()``
    (timed between epoch boundaries: compile + upload sit before the
    first boundary). The caller's builder has already initialized
    ``wf`` (the spliced builders assert tier engagement post-init)."""
    times = []
    inner = wf.decision._on_epoch_ended

    def stamped():
        times.append(time.perf_counter())
        inner()

    wf.decision._on_epoch_ended = stamped
    wf.run()
    deltas = [b - a for a, b in zip(times, times[1:])]
    return n / (sum(deltas) / len(deltas)), deltas


def _spliced_build(data, labels, epochs, transparent):
    """An MNIST784 workflow the FULL fused engine must decline — a
    custom host unit spliced mid-chain:

    - ``transparent=False``: the host unit gives no sweep-transparency
      promise, so it needs per-minibatch slot state — the per-tick
      segment tier (``parallel/segments.py``), composite dispatches
      around the host boundary, per-tick serving;
    - ``transparent=True``: the host unit declares it touches no device
      slots, so the sweep tier (``parallel/sweep.py``) scans the whole
      chain over class sweeps and fires the unit per tick between
      chunk dispatches — full-engine-class dispatch counts."""
    from veles_tpu.core.distributable import TriviallyDistributable
    from veles_tpu.core.units import Unit
    from veles_tpu.parallel.segments import FusedSegment
    from veles_tpu.parallel.sweep import FusedSweep

    class HostObserver(Unit, TriviallyDistributable):
        ticks = 0
        sweep_transparent = transparent

        def run(self):
            type(self).ticks += 1

    wf = _build("auto", data, labels, epochs + 1)
    obs = HostObserver(wf, name="observer")
    fwd1 = wf.forwards[1]
    fwd1.unlink_from(wf.forwards[0])
    obs.link_from(wf.forwards[0])
    fwd1.link_from(obs)
    wf.initialize()
    assert wf.fused_tick is None, "full engine must decline this chain"
    if transparent:
        assert isinstance(getattr(wf, "sweep_unit", None), FusedSweep), \
            "sweep tier did not engage"
    else:
        assert any(isinstance(u, FusedSegment) for u in wf.units), \
            "partial fusion did not engage"
    return wf


def cliff_family(data, labels, epochs=4, repeats=2):
    """Graph mode vs the two fallback fusion tiers, INTERLEAVED and on
    the SAME estimator.

    r3/r4 measured these as one wall-clock run each, graph mode scored
    by min(epoch deltas) but the spliced tiers by the mean — so timing
    jitter penalized only the tiers, and single-shot runs swung +-15%
    between rounds. Here every variant is built fresh and run
    ``repeats`` times in alternating order (chip drift and timing
    jitter hit all of them equally), each run scored by its mean epoch
    interval, and a variant reports its best run + the relative gap
    between runs as the spread."""
    def graph():
        wf = _build(False, data, labels, epochs + 1)
        wf.initialize()
        return wf

    builders = (
        ("graph", graph),
        ("segment", lambda: _spliced_build(data, labels, epochs, False)),
        ("sweep", lambda: _spliced_build(data, labels, epochs, True)),
    )
    n = len(data)
    rates = {name: [] for name, _ in builders}
    for rep in range(repeats):
        for name, builder in (builders if rep % 2 == 0
                              else tuple(reversed(builders))):
            rate = _guarded(lambda: _epoch_rate(builder(), n)[0],
                            fallback=None)
            if rate:
                rates[name].append(rate)
    out = {}
    for name, _ in builders:
        vals = rates[name]
        if not vals:
            out[name] = (None, None)
        else:
            best = max(vals)
            out[name] = (best, round((best - min(vals)) / best, 4))
    return out


def transformer_throughput(n=4096, seq=128, embed=256, heads=8,
                           classes=16, epochs=5):
    """Transformer-epoch training throughput (tokens/sec) through the
    fused attention engine — the first-class sequence path finally gets
    a bench number."""
    from veles_tpu.core import prng
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.standard import StandardWorkflow

    rng = numpy.random.RandomState(0)
    data = rng.randn(n, seq, embed).astype(numpy.float32)
    n_valid = n // 8
    labels = _balanced_labels(rng, classes, n_valid, n - n_valid)
    prng.get("default").seed(5)
    prng.get("loader").seed(5)
    wf = StandardWorkflow(
        DummyLauncher(),
        layers=[{"type": "layer_norm"},
                {"type": "self_attention", "heads": heads,
                 "causal": True},
                {"type": "layer_norm"},
                {"type": "all2all_tanh",
                 "output_sample_shape": (embed,)},
                {"type": "softmax", "output_sample_shape": (classes,)}],
        loader_kwargs=dict(data=data, labels=labels,
                           class_lengths=[0, n // 8, n - n // 8],
                           minibatch_size=64,
                           normalization_type="none"),
        learning_rate=0.01, gradient_moment=0.9,
        decision_kwargs=dict(max_epochs=epochs + 1),
        name="tx-bench")
    wf.initialize()
    times = []
    inner = wf.decision._on_epoch_ended

    def stamped():
        times.append(time.perf_counter())
        inner()

    wf.decision._on_epoch_ended = stamped
    wf.run()
    deltas = [b - a for a, b in zip(times, times[1:])]
    tokens = n * seq
    return tokens / (sum(deltas) / len(deltas)), deltas


def _device_sec_per_iter(scan_builder, init, lengths=(30, 90), repeats=4):
    """DEVICE time per iteration from two scan lengths.

    Wall-clock per dispatch carries per-call constants (dispatch,
    transfer, readback) whose run-to-run swing can dominate a short
    step. Timing a ``lax.scan`` of the step at TWO lengths and dividing
    the difference cancels every such constant on any host;
    min-of-repeats rejects outliers. Returns
    ``(sec_per_iter, rel_spread)`` where rel_spread is the relative gap
    between the two best long-scan repeats — the run-to-run variance
    proxy for the derived number."""
    results = {}
    spreads = []
    for length in lengths:
        fn = scan_builder(length)
        jax.block_until_ready(fn(init))  # compile + warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(init))
            times.append(time.perf_counter() - t0)
        times.sort()
        results[length] = times[0]
        spreads.append((times[1] - times[0]) / times[0])
    l1, l2 = lengths
    return (results[l2] - results[l1]) / (l2 - l1), round(max(spreads), 4)


def fused_step_device(peak):
    """Device-time step cost + derived FLOP throughput of a wide-MLP
    fused train step (the TITAN-anchor number, now on device time)."""
    from veles_tpu.parallel.step import build_train_step

    batch, in_f, hidden, classes = 4096, 784, 4096, 10
    spec = [
        dict(activation="tanh", learning_rate=0.03, learning_rate_bias=0.03,
             weights_decay=0.0, l1_vs_l2=0.0, gradient_moment=0.9),
        dict(activation="linear", learning_rate=0.03,
             learning_rate_bias=0.03, weights_decay=0.0, l1_vs_l2=0.0,
             gradient_moment=0.9),
    ]
    rng = numpy.random.RandomState(0)
    params = {"w": [], "b": [], "vw": [], "vb": []}
    fan_in = in_f
    for width in (hidden, classes):
        params["w"].append(jnp.asarray(
            rng.randn(fan_in, width).astype(numpy.float32) * 0.05))
        params["b"].append(jnp.zeros(width, jnp.float32))
        params["vw"].append(jnp.zeros((fan_in, width), jnp.float32))
        params["vb"].append(jnp.zeros(width, jnp.float32))
        fan_in = width
    data = jnp.asarray(rng.rand(batch, in_f).astype(numpy.float32))
    labels = jnp.asarray(rng.randint(0, classes, batch))
    mask = jnp.ones(batch, jnp.float32)
    step = build_train_step(spec, donate=False)

    def scan_builder(length):
        @jax.jit
        def steps(params):
            def body(p, _):
                p, metrics = step(p, data, labels, mask)
                return p, metrics[0]
            return jax.lax.scan(body, params, None, length=length)
        return steps

    # ~0.8 ms/step: long scans so the per-call constant the difference
    # cancels is small RELATIVE noise too
    sec, spread = _device_sec_per_iter(scan_builder, params,
                                       lengths=(200, 600), repeats=4)
    # honest accounting: the step does NOT compute the first layer's
    # input gradient (parallel/step.py backward skips i==0), so layer 1
    # is forward + weight-grad (4x) and only deeper layers are 6x
    flops_per_image = 4 * in_f * hidden + 6 * hidden * classes
    gflops = batch * flops_per_image / sec / 1e9
    return {"fused_step_device_ms": round(sec * 1000, 4),
            "fused_step_device_spread": spread,
            "fused_step_gflops": round(gflops, 1),
            "fused_step_mfu": _mfu(gflops, peak)}


def alexnet_device(wf, peak, minibatch=128):
    """AlexNet device-time step cost + MFU via the bench workflow's OWN
    compiled ``train_sweep`` (the product sweep function — a lax.scan
    of the train step over minibatch rows) at two row counts. Wrapping
    the jitted train step in a fresh outer scan instead makes the
    remote compiler chew for tens of minutes (the jit-in-jit inline of
    the 11-layer fwd+bwd body); the product sweep's own compile is
    seconds, and the 2x-rows variant reuses the traced body."""
    from veles_tpu.parallel import fused as fz

    tick = wf.fused_tick
    train_sweep = tick._steps_[2]
    norm = tick._norm_
    specs = tick._specs_
    loader = wf.loader
    data = loader.original_data.data
    labels = loader.labels_for_gather()
    hypers = fz.get_hypers(wf)
    rng = numpy.random.RandomState(0)

    def run_sweep(length, params):
        rows = rng.randint(0, len(loader.original_data),
                           (length, minibatch)).astype(numpy.int64)
        sizes = numpy.full(length, minibatch, numpy.int32)
        seeds = numpy.zeros(length, numpy.int64)
        return train_sweep(params, hypers, norm, data, labels, rows,
                           sizes, numpy.float32(length * minibatch),
                           seeds)

    lengths, repeats = (9, 27), 4
    best = {}
    spreads = []
    for length in lengths:
        params = jax.tree.map(jnp.copy, fz.get_params(wf, specs))
        jax.block_until_ready(run_sweep(length, params))  # compile
        times = []
        for _ in range(repeats):
            # train_sweep donates params: re-snapshot per call
            params = jax.tree.map(jnp.copy, fz.get_params(wf, specs))
            t0 = time.perf_counter()
            jax.block_until_ready(run_sweep(length, params))
            times.append(time.perf_counter() - t0)
        times.sort()
        best[length] = times[0]
        spreads.append((times[1] - times[0]) / times[0])
    sec = (best[lengths[1]] - best[lengths[0]]) / (lengths[1]
                                                   - lengths[0])
    gflops = minibatch * ALEXNET_TRAIN_GFLOP_PER_IMAGE / sec
    return {"alexnet_device_ms": round(sec * 1000, 3),
            "alexnet_device_spread": round(max(spreads), 4),
            "alexnet_device_images_per_sec": round(minibatch / sec, 1),
            "alexnet_mfu_device": _mfu(gflops, peak)}


def transformer_device(peak, batch=16, seq=512, embed=1024, heads=16,
                       depth=4, classes=256, mlp_ratio=4):
    """Realistically-sized transformer train step (embed>=1024,
    seq>=512): COMPLETE pre-LN blocks (LN → residual
    attention → LN → residual gelu FFN) through the fused engine, with
    device-time MFU. FLOPs count the materialized matmuls (qkv + scores
    + values + out-proj + the two FFN projections per layer; full S x S
    scores — the attention op masks, it does not skip); backward ~2x
    forward."""
    from veles_tpu.parallel.fused import (_ATTN_LEAVES, _WB_LEAVES,
                                          build_tick)

    hidden = mlp_ratio * embed
    specs = []
    for _ in range(depth):
        specs.append({"kind": "layer_norm", "eps": 1e-5,
                      "leaves": _WB_LEAVES, "has_params": True,
                      "solver": "momentum"})
        specs.append({"kind": "attention", "heads": heads, "causal": True,
                      "residual": True, "leaves": _ATTN_LEAVES,
                      "has_params": True, "solver": "momentum"})
        specs.append({"kind": "layer_norm", "eps": 1e-5,
                      "leaves": _WB_LEAVES, "has_params": True,
                      "solver": "momentum"})
        specs.append({"kind": "ffn", "activation": "gelu",
                      "residual": True, "leaves": _ATTN_LEAVES,
                      "has_params": True, "solver": "momentum"})
    specs.append({"kind": "dense", "activation": "linear",
                  "leaves": _WB_LEAVES, "has_params": True,
                  "solver": "momentum"})
    rng = numpy.random.RandomState(0)

    def leaf(*shape):
        return jnp.asarray(rng.randn(*shape).astype(numpy.float32)
                           * 0.02)

    params = []
    for spec in specs:
        if spec["kind"] == "layer_norm":
            p = {"w": jnp.ones(embed, jnp.float32),
                 "b": jnp.zeros(embed, jnp.float32)}
        elif spec["kind"] == "attention":
            p = {"w": leaf(embed, 3 * embed),
                 "b": jnp.zeros(3 * embed, jnp.float32),
                 "ow": leaf(embed, embed),
                 "ob": jnp.zeros(embed, jnp.float32)}
        elif spec["kind"] == "ffn":
            p = {"w": leaf(embed, hidden),
                 "b": jnp.zeros(hidden, jnp.float32),
                 "ow": leaf(hidden, embed),
                 "ob": jnp.zeros(embed, jnp.float32)}
        else:
            p = {"w": leaf(seq * embed, classes),
                 "b": jnp.zeros(classes, jnp.float32)}
        params.append({"p": p,
                       "v": jax.tree.map(jnp.zeros_like, p)})
    hyper = jnp.asarray([0.01, 0.01, 0.0, 0.0, 0.9, 0.9, 0.999, 1e-8],
                        jnp.float32)
    hypers = [hyper] * len(specs)
    n = 4 * batch
    data = jnp.asarray(rng.randn(n, seq, embed).astype(numpy.float32))
    labels = jnp.asarray(rng.randint(0, classes, n))
    train_step = build_tick(specs, "none", None, with_confusion=False)[0]
    valid = numpy.float32(batch)
    seed = numpy.int64(0)

    def scan_builder(length):
        rows = jnp.asarray(rng.randint(0, n, (length, batch)).astype(
            numpy.int64))

        @jax.jit
        def steps(params):
            def body(p, idx):
                p, (loss, _) = train_step(p, hypers, {}, data, labels,
                                          idx, valid, seed)
                return p, loss
            return jax.lax.scan(body, params, rows)
        return steps

    sec, spread = _device_sec_per_iter(scan_builder, params,
                                       lengths=(20, 60), repeats=5)
    fwd_flops_per_tok = depth * (8 * embed * embed + 4 * seq * embed
                                 + 4 * embed * hidden) \
        + 2 * embed * classes
    train_flops_per_step = 3 * fwd_flops_per_tok * batch * seq
    gflops = train_flops_per_step / sec / 1e9
    return {"transformer_device_ms": round(sec * 1000, 3),
            "transformer_device_spread": spread,
            "transformer_device_tokens_per_sec":
                round(batch * seq / sec, 1),
            "transformer_mfu": _mfu(gflops, peak),
            "transformer_device_config":
                "b%d_s%d_e%d_h%d_L%d_f%d" % (batch, seq, embed, heads,
                                             depth, mlp_ratio)}


def pallas_epilogue_compare():
    """The MEASURED pallas_dense on/off numbers for the
    product dense-layer step (fwd + bwd + SGD update on 784->4096->10,
    mb 4096 — every matmul pallas-eligible). Interleaved two-length
    timing (chip drift hits both variants equally). The result feeds
    docs/performance.md's Pallas section."""
    from veles_tpu.ops.gemm import dense_layer

    batch, in_f, hidden, classes = 4096, 784, 4096, 10
    rng = numpy.random.RandomState(0)
    params = {
        "w0": jnp.asarray(rng.randn(in_f, hidden).astype(numpy.float32)
                          * 0.05),
        "b0": jnp.zeros(hidden, jnp.float32),
        "w1": jnp.asarray(rng.randn(hidden, classes).astype(
            numpy.float32) * 0.05),
        "b1": jnp.zeros(classes, jnp.float32),
    }
    x = jnp.asarray(rng.rand(batch, in_f).astype(numpy.float32))
    labels = jnp.asarray(rng.randint(0, classes, batch))

    def make(use_pallas):
        def loss_fn(p):
            h = dense_layer(x, p["w0"], p["b0"], activation="tanh",
                            use_pallas=use_pallas)
            logits = dense_layer(h, p["w1"], p["b1"],
                                 activation="linear",
                                 use_pallas=use_pallas)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(
                logp, labels[:, None], axis=1))

        def step(p):
            grads = jax.grad(loss_fn)(p)
            return jax.tree.map(lambda w, g: w - 0.01 * g, p, grads)

        def scan_builder(length):
            @jax.jit
            def steps(p):
                def body(c, _):
                    return step(c), ()
                return jax.lax.scan(body, p, None, length=length)[0]
            return steps
        return scan_builder

    lengths = (100, 300)
    variants = {"on": make(True), "off": make(False)}
    fns = {(name, length): builder(length)
           for name, builder in variants.items() for length in lengths}
    for fn in fns.values():
        jax.block_until_ready(fn(params))
    best = {key: float("inf") for key in fns}
    for _ in range(5):
        for key, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(params))
            best[key] = min(best[key], time.perf_counter() - t0)
    span = lengths[1] - lengths[0]
    on = (best[("on", 300)] - best[("on", 100)]) / span
    off = (best[("off", 300)] - best[("off", 100)]) / span
    return {"pallas_epilogue_on_ms": round(on * 1000, 4),
            "pallas_epilogue_off_ms": round(off * 1000, 4),
            "pallas_epilogue_speedup": round(off / on, 3)}


def longctx_device(batch=1, seq=8192, embed=1024, heads=8):
    """Long-context attention-block forward at b1/s8192/hd128 — the
    flash-attention tier (``ops/attention._use_pallas_flash`` gates the
    Pallas kernel to sequences >=4096, where it measured faster than
    XLA). The auto-engaged flash path and the forced-XLA path are
    timed INTERLEAVED, so ``longctx_pallas_speedup`` is the product
    Pallas win the >=4096 gate buys (the auto-engage +
    measured-crossover doctrine, evidenced on-artifact). Forward-only:
    the backward flash compile takes many minutes
    at this length, and the long-context serving story is what this key
    evidences; multi-chip long-sequence TRAINING rides ring attention
    (``ops/attention.ring_attention``, dryrun-validated)."""
    from veles_tpu.ops import attention as attn_mod
    from veles_tpu.ops.attention import attention_block

    rng = numpy.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, seq, embed).astype(numpy.float32)
                    * 0.1)
    w = jnp.asarray(rng.randn(embed, 3 * embed).astype(numpy.float32)
                    * 0.02)
    b = jnp.zeros(3 * embed, jnp.float32)
    ow = jnp.asarray(rng.randn(embed, embed).astype(numpy.float32)
                     * 0.02)
    ob = jnp.zeros(embed, jnp.float32)

    def scan_builder(length):
        @jax.jit
        def scan(x0):
            def body(c, _):
                y = attention_block(c, w, b, ow, ob, heads, True)
                return c + 0.001 * y, ()
            return jnp.sum(jax.lax.scan(body, x0, None,
                                        length=length)[0])
        return scan

    lengths = (30, 90)
    fns = {}
    saved = attn_mod.FORCE_FLASH
    try:
        for name, flag in (("flash", None), ("xla", False)):
            # flag None = the PRODUCT auto-gate (engages at seq 8192)
            attn_mod.FORCE_FLASH = flag
            for length in lengths:
                fn = scan_builder(length)
                float(fn(x))  # compile + warm under this gate state
                fns[(name, length)] = lambda fn=fn: float(fn(x))
    finally:
        attn_mod.FORCE_FLASH = saved
    timed = _two_length_times(fns, lengths)
    sec, spread = timed["flash"]
    xla_sec, xla_spread = timed["xla"]
    return {"longctx_fwd_block_ms": round(sec * 1000, 3),
            "longctx_fwd_spread": spread,
            "longctx_xla_block_ms": round(xla_sec * 1000, 3),
            "longctx_xla_spread": xla_spread,
            "longctx_pallas_speedup": round(xla_sec / sec, 3),
            "longctx_config": "b%d_s%d_e%d_h%d_flash" % (batch, seq,
                                                         embed, heads)}


def _cpu8_env():
    """Environment for an 8-device virtual-CPU child bench: the parent
    holds the chip, so the child is forced onto the host platform. One
    helper for every CPU-8 subprocess section (``pod_cpu8_tick_ms``,
    ``reshard_bench``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.abspath(__file__))]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    return env


def pod_overhead():
    """Prove the pod-mode wrapper costs ~nothing at n=1.

    The SAME wide-MLP train step as the flagship fused-step number,
    plain jit vs shard_map over a one-device ``data=1`` mesh on the
    real chip — device-time per step for each, and the relative
    overhead. The wrapper cost is a near-constant ~10 us/step (the
    n=1 shard_map program keeps its reshard boilerplate), so the
    honest claim is relative to a production-sized step, not a toy
    one. Plus the composed fleet x pod dispatch cost: a subprocess on
    8 virtual CPU devices measures the per-tick wall cost of the
    dp8-sharded step (the slave-tick shape — per-tick dispatch, no
    scan, tiny shapes) so the fleet x pod path has a recorded dispatch
    number."""
    import subprocess
    import sys

    from veles_tpu.parallel.mesh import build_mesh
    from veles_tpu.parallel.step import build_train_step

    batch, in_f, hidden, classes = 4096, 784, 4096, 10
    spec = [
        dict(activation="tanh", learning_rate=0.03, learning_rate_bias=0.03,
             weights_decay=0.0, l1_vs_l2=0.0, gradient_moment=0.9),
        dict(activation="linear", learning_rate=0.03,
             learning_rate_bias=0.03, weights_decay=0.0, l1_vs_l2=0.0,
             gradient_moment=0.9),
    ]
    rng = numpy.random.RandomState(0)
    params = {"w": [], "b": [], "vw": [], "vb": []}
    fan_in = in_f
    for width in (hidden, classes):
        params["w"].append(jnp.asarray(
            rng.randn(fan_in, width).astype(numpy.float32) * 0.05))
        params["b"].append(jnp.zeros(width, jnp.float32))
        params["vw"].append(jnp.zeros((fan_in, width), jnp.float32))
        params["vb"].append(jnp.zeros(width, jnp.float32))
        fan_in = width
    data = jnp.asarray(rng.rand(batch, in_f).astype(numpy.float32))
    labels = jnp.asarray(rng.randint(0, classes, batch))
    mask = jnp.ones(batch, jnp.float32)

    def scans(mesh):
        step = build_train_step(spec, mesh=mesh, donate=False)

        def scan_builder(length):
            @jax.jit
            def steps(params):
                def body(p, _):
                    p, metrics = step(p, data, labels, mask)
                    return p, metrics[0]
                return jax.lax.scan(body, params, None, length=length)
            return steps
        return scan_builder

    # INTERLEAVED two-length timing: the chip's throughput
    # itself drifts several percent over minutes, so timing plain and
    # meshed back-to-back within each repeat is the only way a
    # ~us-scale overhead survives the comparison
    mesh = build_mesh(devices=jax.devices()[:1], data=1)
    lengths = (400, 1200)
    variants = {"plain": scans(None), "mesh": scans(mesh)}
    fns = {(name, length): builder(length)
           for name, builder in variants.items() for length in lengths}
    for fn in fns.values():
        jax.block_until_ready(fn(params))  # compile + warm
    best = {key: float("inf") for key in fns}
    order = list(fns)
    for rep in range(10):
        # alternate the visit order so a monotone chip-speed drift
        # within the round cannot bias one variant
        for key in (order if rep % 2 == 0 else reversed(order)):
            fn = fns[key]
            t0 = time.perf_counter()
            jax.block_until_ready(fn(params))
            best[key] = min(best[key], time.perf_counter() - t0)
    span = lengths[1] - lengths[0]
    plain = (best[("plain", 1200)] - best[("plain", 400)]) / span
    meshed = (best[("mesh", 1200)] - best[("mesh", 400)]) / span
    out = {"pod_n1_plain_device_ms": round(plain * 1000, 4),
           "pod_n1_mesh_device_ms": round(meshed * 1000, 4),
           "pod_n1_overhead_pct": round((meshed - plain) / plain * 100,
                                        2)}
    child = (
        "import time, numpy, jax, jax.numpy as jnp\n"
        "from veles_tpu.parallel.mesh import build_mesh\n"
        "from veles_tpu.parallel.step import build_train_step\n"
        "spec=[dict(activation='tanh',learning_rate=.03,"
        "learning_rate_bias=.03,weights_decay=0.,l1_vs_l2=0.,"
        "gradient_moment=.9)]*2\n"
        "rng=numpy.random.RandomState(0)\n"
        "params={'w':[],'b':[],'vw':[],'vb':[]}\n"
        "fan=64\n"
        "for width in (32,10):\n"
        "    params['w'].append(jnp.asarray(rng.randn(fan,width)"
        ".astype(numpy.float32)*.05))\n"
        "    params['b'].append(jnp.zeros(width,jnp.float32))\n"
        "    params['vw'].append(jnp.zeros((fan,width),jnp.float32))\n"
        "    params['vb'].append(jnp.zeros(width,jnp.float32))\n"
        "    fan=width\n"
        "mesh=build_mesh(data=8)\n"
        "step=build_train_step(spec,mesh=mesh,donate=False)\n"
        "data=jnp.asarray(rng.rand(64,64).astype(numpy.float32))\n"
        "labels=jnp.asarray(rng.randint(0,10,64))\n"
        "mask=jnp.ones(64,jnp.float32)\n"
        "p,m=step(params,data,labels,mask); jax.block_until_ready(m)\n"
        "t0=time.perf_counter()\n"
        "for _ in range(100):\n"
        "    p,m=step(p,data,labels,mask)\n"
        "jax.block_until_ready(m)\n"
        "print((time.perf_counter()-t0)*10)\n")
    proc = subprocess.run([sys.executable, "-c", child], env=_cpu8_env(),
                          capture_output=True, text=True, timeout=600)
    if proc.returncode == 0:
        out["pod_cpu8_tick_ms"] = round(
            float(proc.stdout.strip().splitlines()[-1]), 3)
    else:
        print(proc.stderr[-2000:], file=sys.stderr)
        out["pod_cpu8_tick_ms"] = None
    return out


#: AlexNet-227 single-tower training FLOPs per image: forward ≈0.72
#: GMAC (conv1 105M + conv2 223M + conv3 149M + conv4 112M + conv5 74M
#: + fc 59M) = 1.45 GFLOP; backward ≈2x forward → ≈4.3 GFLOP/img
ALEXNET_TRAIN_GFLOP_PER_IMAGE = 4.3


def alexnet_throughput(n_valid=1000, n_train=2000, epochs=8):
    """Full-size AlexNet-227 (single tower, 1000-way) images/sec through
    the fused workflow path — the BASELINE ImageNet-AlexNet axis
    (synthetic pixels; the arithmetic is identical to real ones).

    Splits are exactly proportional over the 1000 classes (valid one
    per class, train two per class) so the loader's label-stats checks
    pass clean."""
    from veles_tpu.core import prng
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.alexnet import AlexNetWorkflow

    assert n_valid % 1000 == 0 and n_train % 1000 == 0
    rng = numpy.random.RandomState(0)
    n = n_valid + n_train
    data = (rng.rand(n, 227, 227, 3) * 255).astype(numpy.float32)
    labels = _balanced_labels(rng, 1000, n_valid, n_train)
    prng.get("default").seed(1)
    prng.get("loader").seed(1)
    wf = AlexNetWorkflow(
        DummyLauncher(), n_classes=1000,
        loader_kwargs=dict(data=data, labels=labels,
                           class_lengths=[0, n_valid, n_train],
                           minibatch_size=128,
                           normalization_type="mean_disp"),
        decision_kwargs=dict(max_epochs=epochs + 1),
        name="alexnet-bench")
    wf.initialize()
    times = []
    inner = wf.decision._on_epoch_ended

    def stamped():
        times.append(time.perf_counter())
        inner()

    wf.decision._on_epoch_ended = stamped
    wf.run()
    # mean, not min: the default pipelined path lets the host burst
    # ahead of the device, so min would pick a dishonest interval
    deltas = [b - a for a, b in zip(times, times[1:])]
    return n / (sum(deltas) / len(deltas)), [n / d for d in deltas], wf



def _two_length_times(fns, lengths, repeats=6, warmup=1):
    """min-of-repeats two-length slope timing for a dict of compiled
    zero-arg runners keyed (variant, length) — ONE shared copy of the
    decode-bench scaffold, and the timing loop visits every runner
    round-robin (alternating direction) so chip drift and timing
    jitter hit all compared variants equally. Callers must have
    compiled+warmed each runner (trace-time state like
    quant.FORCE_PALLAS is baked at compile).

    ``warmup`` untimed round-robin passes run first: the compile-time
    warm call leaves caches (device queues, XLA
    allocator pools) in a different state than steady dispatch, and
    the first timed visit used to eat that cost — the r5 decode keys'
    0.38-0.46 spreads were exactly this first-visit tax landing on
    whichever variant went first. Returns
    {variant: (sec_per_iter, rel_spread)}."""
    times = {key: [] for key in fns}
    order = list(fns)
    for _ in range(warmup):
        for key in order:
            fns[key]()
    for rep in range(repeats):
        for key in (order if rep % 2 == 0 else reversed(order)):
            t0 = time.perf_counter()
            fns[key]()
            times[key].append(time.perf_counter() - t0)
    out = {}
    variants = {name for name, _ in fns}
    for name in variants:
        results, spreads = {}, []
        for length in lengths:
            ts = sorted(times[(name, length)])
            results[length] = ts[0]
            spreads.append((ts[1] - ts[0]) / ts[0])
        sec = (results[lengths[1]] - results[lengths[0]]) \
            / (lengths[1] - lengths[0])
        out[name] = (sec, round(max(spreads), 4))
    return out


def decode_device(batch=8, prompt=512, embed=1024, heads=16, blocks=4,
                  vocab=32768, dtype=None):
    """KV-cache greedy decode throughput (the serving side of the
    long-context tier — ``parallel/decode.py``): steady-state tokens/sec
    at a realistic config, prefill + dispatch costs cancelled by the
    two-length scan timing. ``dtype=bfloat16`` halves the weight + cache
    traffic of the memory-bound loop (measured +~50% tokens/sec)."""
    from veles_tpu.parallel.decode import (decode_step, init_kv_cache,
                                           prefill)
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.02)
    key_prefix = "decode"
    if dtype is not None:
        params = jax.tree.map(lambda a: a.astype(dtype), params)
        table = table.astype(dtype)
        key_prefix = "decode_%s" % jnp.dtype(dtype).name
    toks = jnp.asarray(rng.randint(0, vocab, (batch, prompt)))
    # headroom must cover the LONGEST timing scan (576 steps below):
    # short slots would clamp dynamic_update_slice writes and time a
    # program decoding garbage
    cache0 = init_kv_cache(blocks, batch, prompt + 608, heads,
                           embed // heads,
                           dtype=dtype or jnp.float32)
    logits0, cache0 = jax.jit(prefill, static_argnames="heads")(
        params, table[toks], heads, cache0)

    def scan_builder(length):
        # params/table ride as ARGUMENTS: closing over them would bake
        # 128+ MB of weights into the HLO as constants
        @jax.jit
        def steps(state):
            params, table, cache, logits = state

            def body(carry, _):
                cache, logits = carry
                tok = jnp.argmax(logits, axis=-1)
                x_tok = table[tok][:, None, :]
                logits, cache = decode_step(params, x_tok, heads, cache)
                return (cache, logits), ()

            (cache, logits), _ = jax.lax.scan(body, (cache, logits),
                                              None, length=length)
            # scalar result: the timing loop MATERIALIZES it — the
            # fence is the device->host read (constant-size, cancelled
            # by the two-length subtraction)
            return jnp.sum(logits.astype(jnp.float32))
        return steps

    state = (params, table, cache0, logits0)
    # r4's (16, 272)x4 spread was 0.56: a 16-step scan is ~12 ms —
    # per-call-constant territory. Long scans (~50/~400 ms fp32) put
    # the measured quantity well above that jitter; min-of-6 rejects
    # the remaining outliers
    lengths = (64, 576)
    fns = {}
    for length in lengths:
        fn = scan_builder(length)
        float(fn(state))  # compile + warm
        fns[("decode", length)] = lambda fn=fn: float(fn(state))
    # the noisy-keys satellite: extra untimed warm passes + a deeper
    # min-of-N for the decode timers (r5 spreads sat at 0.38-0.46
    # while everything else held <= 0.01)
    sec, spread = _two_length_times(fns, lengths, repeats=8,
                                    warmup=2)["decode"]
    return {key_prefix + "_step_ms": round(sec * 1000, 3),
            key_prefix + "_spread": spread,
            key_prefix + "_tokens_per_sec": round(batch / sec, 1),
            key_prefix + "_config": "b%d_p%d_e%d_h%d_L%d_v%d"
                                    % (batch, prompt, embed, heads,
                                       blocks, vocab)}


def decode_int8_device(batch=8, prompt=512, embed=1024, heads=16,
                       blocks=4, vocab=32768, kv_quant=False):
    """The int8 serving tier (the Pallas product-path
    win): weight-only int8 decode via the dequant-fused Pallas matvec
    (``ops/quant.py``), measured INTERLEAVED against the XLA dequant
    formulation of the same quantized math. Cache/activations bf16
    (the bf16 tier's config); weights are the int8 halves of its HBM
    traffic; ``kv_quant`` additionally stores the KV cache as int8
    (the decode_int8kv_* keys — the other half of the traffic). Keys:
    tokens/sec on the product auto path and, for the weight tier, with
    the Pallas matvec forced on, interleaved — the speedup key records
    what forcing buys (sub-1 = the gate is right to keep XLA). The
    int8-KV tier has no forced twin: its per-batch Pallas attend lost
    on record (``decode_int8kv_pallas_speedup`` 0.977, BENCH_r05),
    could not compile with per-row masks, and was deleted (PR 21)."""
    from veles_tpu.ops import quant
    from veles_tpu.parallel.decode import (decode_step, init_kv_cache,
                                           prefill, quantize_params)
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    qparams = quantize_params(params)

    # activations-side leaves (norms, biases) go bf16; int8 weights and
    # their f32 dequant scales keep their dtypes
    def cast(path, a):
        if a.dtype == jnp.float32 and not any(
                getattr(k, "key", None) == "scale" for k in path):
            return a.astype(jnp.bfloat16)
        return a

    qparams = jax.tree_util.tree_map_with_path(cast, qparams)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.02).astype(jnp.bfloat16)
    toks = jnp.asarray(rng.randint(0, vocab, (batch, prompt)))
    # +640 (not 608): the quantized cache's T tiles whole 128 lanes
    # (512+640=1152)
    cache0 = init_kv_cache(blocks, batch, prompt + 640, heads,
                           embed // heads, dtype=jnp.bfloat16,
                           quantized=kv_quant)
    logits0, cache0 = jax.jit(prefill, static_argnames="heads")(
        qparams, table[toks], heads, cache0)

    def scan_builder(length):
        # a FRESH jit per (variant, length): the Pallas/XLA choice is
        # trace-time module state (quant.PALLAS_MAX_ROWS below), so the
        # variant is baked in at this compile
        @jax.jit
        def steps(state):
            params, table, cache, logits = state

            def body(carry, _):
                cache, logits = carry
                tok = jnp.argmax(logits, axis=-1)
                x_tok = table[tok][:, None, :]
                logits, cache = decode_step(params, x_tok, heads, cache)
                return (cache, logits), ()

            (cache, logits), _ = jax.lax.scan(body, (cache, logits),
                                              None, length=length)
            return jnp.sum(logits.astype(jnp.float32))
        return steps

    state = (qparams, table, cache0, logits0)
    out = {}
    prefix = "decode_int8kv" if kv_quant else "decode_int8"
    lengths = (64, 576)
    fns = {}
    saved = quant.FORCE_PALLAS
    # "" = the PRODUCT auto path (the matvec behind its measured-win
    # gate); "_pallas" = the matvec forced ON (weight tier only). The
    # speedup key records what forcing the kernel buys (sub-1 = it
    # loses, the honest doctrine record).
    variants = (("", None),) if kv_quant else (("", None),
                                               ("_pallas", True))
    try:
        for name, flag in variants:
            # the Pallas/XLA choice bakes in at trace time: compile
            # each variant's scans under its flag, THEN time them all
            # interleaved (chip drift hits both variants equally)
            quant.FORCE_PALLAS = flag
            for length in lengths:
                fn = scan_builder(length)
                float(fn(state))  # compile + warm under this flag
                fns[(name, length)] = lambda fn=fn: float(fn(state))
    finally:
        quant.FORCE_PALLAS = saved
    # same noisy-keys treatment as decode_device: warm passes +
    # min-of-8 (the int8/int8kv auto-path spreads were the r5 outliers)
    for name, (sec, spread) in _two_length_times(
            fns, lengths, repeats=8, warmup=2).items():
        out["%s%s_step_ms" % (prefix, name)] = round(sec * 1000, 3)
        out["%s%s_spread" % (prefix, name)] = spread
        out["%s%s_tokens_per_sec" % (prefix, name)] = round(
            batch / sec, 1)
    auto = out.get(prefix + "_step_ms")
    forced = out.get(prefix + "_pallas_step_ms")
    if auto and forced:
        out[prefix + "_pallas_speedup"] = round(auto / forced, 3)
    out[prefix + "_config"] = "b%d_p%d_e%d_h%d_L%d_v%d" % (
        batch, prompt, embed, heads, blocks, vocab)
    return out


def decode_continuous(slots=8, prompt=512, budget=64, n_requests=16,
                      embed=1024, heads=16, blocks=4, vocab=32768,
                      chunk=64, quantize=None):
    """Continuous-batching serving throughput: the
    ContinuousDecoder drains ``n_requests`` STAGGERED bf16 requests
    (new prompts admitted as slots free up mid-flight) in chunked
    throughput mode. Wall-clock tokens/sec — includes admission
    prefills and the one host round trip per ``chunk`` tokens; best of
    two runs with the run gap as spread.

    Extra observability keys (the PR-3 serving-gap trajectory):
    ``decode_continuous_prefill_ms`` is the best run's total
    host-blocking admission (bucket prefill) wall time, and
    ``decode_continuous_host_overhead_fraction`` is the share of the
    run's wall clock spent OUTSIDE device-facing calls (dispatch,
    readback, admit) — pure host bookkeeping; near 0 means the device
    queue stays fed. ``quantize`` forwards to the decoder (the int8 /
    int8-KV slot tiers).

    Request-latency keys (the request-truth observability PR): a
    RequestLedger rides the staggered run, so per-request
    ``decode_continuous_ttft_p50/p95/p99_ms`` (submit -> first token,
    from the ledger's stage stamps) and
    ``decode_continuous_tpot_p95_ms`` (per-token chunk-collect
    cadence) land in the artifact beside tokens/sec — all lower-better
    under ``make regress``'s ``_ms`` rule."""
    from veles_tpu.observe.reqledger import RequestLedger
    from veles_tpu.observe.slo import row_latencies
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import ContinuousDecoder

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.02).astype(jnp.bfloat16)
    prompts = [rng.randint(0, vocab, prompt) for _ in range(n_requests)]

    def run():
        # +2 chunks of headroom: the lag-1 pipelined drain lets a
        # finished slot decode one extra chunk before it recycles
        ledger = RequestLedger(capacity=2 * n_requests)
        dec = ContinuousDecoder(params, table, heads, slots=slots,
                                max_len=prompt + budget + 2 * chunk,
                                n_tokens=budget, quantize=quantize,
                                ledger=ledger)
        rows = {}

        def submit_one():
            rid = dec.submit(pending.pop())
            rows[rid] = ledger.stage(api="bench", prompt_len=prompt,
                                     budget=budget)
            dec.ledger_link(rid, rows[rid])

        def progress():
            # resolve completed rows within one pass of their last
            # chunk (the tpot fallback spans first_token -> resolved),
            # then keep the stagger fed
            for rid in [r for r in rows if dec.done(r)]:
                ledger.resolve(rows.pop(rid), "completed")
            if pending:
                submit_one()

        # stagger: half the requests up front, the rest trickle in as
        # chunks complete (joining mid-flight is the tier's point)
        pending = list(prompts)
        for _ in range(min(slots, len(pending))):
            submit_one()
        t0 = time.perf_counter()
        dec.drain_pipelined(chunk, admit=progress)
        dt = time.perf_counter() - t0
        for rid in list(rows):
            ledger.resolve(rows.pop(rid), "completed")
        latencies = [row_latencies(row)
                     for row in ledger.slowest(2 * n_requests)]
        return (dec.tokens_out / dt, dt, dict(dec.timings),
                dict(dec.dispatch_counts), latencies)

    def percentile_ms(values, q):
        if not values:
            return None
        ordered = sorted(values)
        index = min(len(ordered) - 1,
                    int(math.ceil(q * (len(ordered) - 1))))
        return round(ordered[index] * 1000.0, 3)

    run()  # compile (admit + chunk programs) + warm
    runs = [run() for _ in range(2)]
    best_rate, wall, timings, dispatch_counts, latencies = max(
        runs, key=lambda r: r[0])
    ttfts = [t for t, _ in latencies if t is not None]
    tpots = [t for _, t in latencies if t is not None]
    device_s = sum(timings.values())
    prefix = ("decode_continuous" if not quantize
              else "decode_continuous_" + quantize.replace("-", ""))
    return {prefix + "_tokens_per_sec": round(best_rate, 1),
            prefix + "_spread": round(
                (best_rate - min(r[0] for r in runs)) / best_rate, 4),
            prefix + "_ttft_p50_ms": percentile_ms(ttfts, 0.5),
            prefix + "_ttft_p95_ms": percentile_ms(ttfts, 0.95),
            prefix + "_ttft_p99_ms": percentile_ms(ttfts, 0.99),
            prefix + "_tpot_p95_ms": percentile_ms(tpots, 0.95),
            prefix + "_prefill_ms": round(
                timings["admit_s"] * 1000, 3),
            prefix + "_host_overhead_fraction": round(
                max(0.0, 1.0 - device_s / wall), 4),
            # host-overhead attribution between rounds (observability
            # PR): the best run's per-family host-blocking wall ms and
            # its dispatch tallies persist into the BENCH json
            prefix + "_host_ms": {
                key[:-2] if key.endswith("_s") else key:
                    round(sec * 1000, 3)
                for key, sec in sorted(timings.items())},
            prefix + "_dispatch_counts": dispatch_counts,
            prefix + "_config":
                "s%d_p%d_b%d_r%d_c%d_e%d_h%d_L%d_v%d"
                % (slots, prompt, budget, n_requests, chunk, embed,
                   heads, blocks, vocab)}


def decode_paged(embed=256, heads=8, blocks=2, vocab=2048,
                 page_size=128, slots=4, budget=24, chunk=8,
                 lengths=(128, 256, 512), repeats=5):
    """The paged-KV serving section (docs/paged_kv.md, ROADMAP item 2):
    the page-pool slot engine measured against the dense slab it
    replaces, three claims, three key families — all registered
    direction-aware in ``observe/regress.py`` so ``make regress``
    guards them:

    - **length flatness**: per-step decode time with one live sequence
      at each length in ``lengths`` (``decode_{paged,dense}_step_
      len<L>_ms``, min-of-``repeats``), summarized as the max/min ratio
      ``decode_{paged,dense}_step_flatness`` (lower is better; ~1.0
      means the step cost tracks live tokens, not the slab).
    - **admission**: host-blocking admit wall for a page-aligned prompt
      cold vs prefix-cached (``decode_paged_admit_{cold,hit}_ms``,
      programs pre-compiled), summarized as
      ``decode_paged_admit_hit_fraction`` = hit/cold (lower is better;
      the acceptance bar is < 0.1 — a cached system prompt admits for
      ~free).
    - **concurrency at fixed HBM**: the dense slab pins ``slots``
      concurrent sequences no matter how short they are; the pool holds
      whatever fits in LIVE pages. Same KV positions both sides
      (``pool = slots x max_len / page_size``), short prompts admitted
      until the pool refuses: ``decode_{dense,paged}_max_slots`` and
      ``decode_paged_concurrency_gain`` (higher is better).

    Plus ``decode_paged_tokens_per_sec``: the ``decode_continuous``
    staggered-drain recipe on the paged engine with a shared system
    prompt, so the prefix cache works a realistic mix (its hit rate
    lands in ``decode_paged_prefix_hit_rate``)."""
    from veles_tpu.parallel.kv_pool import default_pool_pages, pages_for
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import ContinuousDecoder

    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.02).astype(jnp.bfloat16)
    max_len = max(lengths) + budget + 2 * chunk
    out = {}

    # -- 1) step-time sweep: one live sequence at each length ---------
    def step_ms(paged, live):
        dec = ContinuousDecoder(
            params, table, heads, slots=2, max_len=max_len,
            n_tokens=budget, paged=paged, page_size=page_size)
        dec.submit(rng.randint(0, vocab, live), budget)
        dec.step()  # admit + compile the step program at this span
        dec.step()  # untimed warmup: steady-state caches, no compile
        dec.step()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            dec.step()
            times.append(time.perf_counter() - t0)
        return min(times) * 1000
    for kind, paged in (("dense", False), ("paged", True)):
        per_len = [step_ms(paged, live) for live in lengths]
        for live, ms in zip(lengths, per_len):
            out["decode_%s_step_len%d_ms" % (kind, live)] = round(ms, 3)
        out["decode_%s_step_flatness" % kind] = round(
            max(per_len) / max(min(per_len), 1e-9), 4)

    # -- 2) admission: cold prefill vs prefix-cache hit ---------------
    # min-of-``repeats`` over DISTINCT page-aligned prompts (same
    # bucket, so one compiled program each side): a repeated cold
    # admission of one prompt would itself hit the cache, and a single
    # shot is hostage to host noise. The pool is sized so the cold
    # sweep's cached pages never evict before their hit re-admission.
    systems = [rng.randint(0, vocab, 2 * page_size)
               for _ in range(repeats)]
    warm = rng.randint(0, vocab, 2 * page_size)

    def admit_ms(dec, prompt):
        before = dec.timings["admit_s"]
        rid = dec.submit(prompt, 1)
        dec.step()
        ms = (dec.timings["admit_s"] - before) * 1000
        dec.run_until_drained()
        dec.results.pop(rid, None)
        return ms
    dec = ContinuousDecoder(
        params, table, heads, slots=2, max_len=max_len,
        n_tokens=budget, paged=True, page_size=page_size,
        pool_pages=(2 * pages_for(max_len, page_size)
                    + 2 * (repeats + 1) + 1))
    admit_ms(dec, warm)    # compile the cold-admit program
    admit_ms(dec, warm)    # ... and the hit program (warm is cached)
    cold = min(admit_ms(dec, s) for s in systems)
    hit = min(admit_ms(dec, s) for s in systems)
    out["decode_paged_admit_cold_ms"] = round(cold, 3)
    out["decode_paged_admit_hit_ms"] = round(hit, 3)
    out["decode_paged_admit_hit_fraction"] = round(
        hit / max(cold, 1e-9), 4)

    # -- 3) concurrency at fixed HBM ----------------------------------
    pool_pages = default_pool_pages(slots, max_len, page_size)
    short = 32  # live pages per request: ceil((short + chunk)/ps)
    per_req = pages_for(short + chunk, page_size)
    wide = ContinuousDecoder(
        params, table, heads, slots=(pool_pages - 1) // per_req + 1,
        max_len=max_len, n_tokens=budget, paged=True,
        page_size=page_size, pool_pages=pool_pages)
    for _ in range((pool_pages - 1) // per_req + 1):
        wide.submit(rng.randint(0, vocab, short), budget)
    wide.step()
    out["decode_dense_max_slots"] = slots
    out["decode_paged_max_slots"] = len(wide._slot_req)
    out["decode_paged_concurrency_gain"] = round(
        len(wide._slot_req) / max(slots, 1), 4)

    # -- 4) throughput: the staggered drain with a shared prefix ------
    tails = [rng.randint(0, vocab, 24 + 8 * i) for i in range(8)]
    prompts = [numpy.concatenate([systems[0], t]) for t in tails]

    drain_max = 2 * page_size + 96 + budget + 2 * chunk
    # ONE cache across runs (the breaker-rebuild adoption path): the
    # warmup run cold-prefills the system prompt once, the timed runs
    # admit it as hits — the steady state a long-lived server sees
    shared_cache = None

    last_dec = None

    def run():
        nonlocal shared_cache, last_dec
        if last_dec is not None:
            # the rebuild prelude GenerateAPI._rebuild runs: shadows
            # are captured from the decoder being retired, not per
            # cold admission
            last_dec.pool.capture_shadows(last_dec.state)
        dec = ContinuousDecoder(params, table, heads, slots=slots,
                                max_len=drain_max, n_tokens=budget,
                                paged=True, page_size=page_size,
                                prefix_cache=shared_cache)
        shared_cache = dec.pool.cache
        last_dec = dec
        pending = list(prompts)
        for _ in range(min(slots, len(pending))):
            dec.submit(pending.pop())
        t0 = time.perf_counter()
        dec.drain_pipelined(
            chunk, admit=lambda: pending and dec.submit(pending.pop()))
        dt = time.perf_counter() - t0
        return dec.tokens_out / dt, dec.pool.snapshot()

    run()  # compile + seed the prefix cache
    runs = [run() for _ in range(2)]
    best_rate, pool_snap = max(runs, key=lambda r: r[0])
    out["decode_paged_tokens_per_sec"] = round(best_rate, 1)
    out["decode_paged_spread"] = round(
        (best_rate - min(r[0] for r in runs)) / best_rate, 4)
    if pool_snap["prefix_hit_rate"] is not None:
        out["decode_paged_prefix_hit_rate"] = pool_snap["prefix_hit_rate"]
    out["decode_paged_config"] = (
        "s%d_ps%d_b%d_c%d_L%d_e%d_h%d_v%d_len%s"
        % (slots, page_size, budget, chunk, blocks, embed, heads,
           vocab, "x".join(str(n) for n in lengths)))
    return out


def decode_paged_kernel(embed=64, heads=8, blocks=2, vocab=512,
                        page_size=None, budget=8, lengths=None,
                        repeats=3):
    """The fused paged-attention kernel section (docs/paged_kv.md "The
    fused kernel", ROADMAP item 5): the Pallas kernel tier measured
    against the page-table gather it replaces, same decoder, same
    traffic — two claims:

    - **length flatness**: per-step decode time with one live sequence
      at each length (``decode_paged_kernel_step_len<L>_ms``,
      min-of-``repeats``), summarized as the max/min ratio
      ``decode_paged_kernel_step_flatness`` (lower is better; the
      kernel walks live pages only, so step cost should track live
      tokens — the gather path's cost tracks the page bucket).
    - **mixed-length speedup**: one step over slots live at EVERY
      length at once — the ragged occupancy a real server holds —
      kernel vs gather (``decode_paged_{kernel,gather}_step_mixed_ms``
      and ``decode_paged_kernel_speedup`` = gather/kernel, higher is
      better; > 1 is the win the waste counters predict).

    Both sides run through ``ContinuousDecoder`` with the probe FORCED
    (``ops.paged_attention.FORCE_PAGED_KERNEL`` + ``jax.clear_caches``
    — the jitted step reads the probe at trace time), so the numbers
    include the full dispatch path, not a bare kernel microbench. Off
    TPU the kernel runs in Pallas interpret mode: correct but
    emulated, so the speedup key is only a hardware claim on TPU
    (``decode_paged_kernel_config`` records the backend). Directions
    ride the registered ``_ms``/``_flatness`` lower-better and
    ``_speedup`` higher-better suffixes (observe/regress.py)."""
    from veles_tpu.ops import paged_attention as pgatt
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import ContinuousDecoder

    on_tpu = platform.on_tpu()
    if page_size is None:
        # the TPU construction check requires span-tile multiples;
        # interpret mode off-TPU keeps the sweep small instead
        page_size = 128 if on_tpu else 16
    if lengths is None:
        lengths = ((128, 256, 512) if on_tpu else (16, 48, 96))
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.02).astype(jnp.bfloat16)
    max_len = max(lengths) + budget + 4
    out = {}

    def step_ms(force, lens):
        pgatt.FORCE_PAGED_KERNEL = force
        jax.clear_caches()
        dec = ContinuousDecoder(
            params, table, heads, slots=len(lens), max_len=max_len,
            n_tokens=budget, paged=True, page_size=page_size)
        for live in lens:
            dec.submit(rng.randint(0, vocab, live), budget)
        dec.step()  # admit + compile the step program
        dec.step()  # untimed warmup: steady-state caches, no compile
        dec.step()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            dec.step()
            times.append(time.perf_counter() - t0)
        return min(times) * 1000

    force_prev = pgatt.FORCE_PAGED_KERNEL
    try:
        per_len = [step_ms(True, [live]) for live in lengths]
        for live, ms in zip(lengths, per_len):
            out["decode_paged_kernel_step_len%d_ms" % live] = round(
                ms, 3)
        out["decode_paged_kernel_step_flatness"] = round(
            max(per_len) / max(min(per_len), 1e-9), 4)
        mixed = list(lengths)
        kernel_ms = step_ms(True, mixed)
        gather_ms = step_ms(False, mixed)
        out["decode_paged_kernel_step_mixed_ms"] = round(kernel_ms, 3)
        out["decode_paged_gather_step_mixed_ms"] = round(gather_ms, 3)
        out["decode_paged_kernel_speedup"] = round(
            gather_ms / max(kernel_ms, 1e-9), 4)
    finally:
        pgatt.FORCE_PAGED_KERNEL = force_prev
        jax.clear_caches()
    out["decode_paged_kernel_config"] = (
        "%s_ps%d_b%d_L%d_e%d_h%d_v%d_len%s"
        % (jax.default_backend(), page_size, budget, blocks, embed,
           heads, vocab, "x".join(str(n) for n in lengths)))
    return out


def moe_rows_sweep(rows=(64, 128, 256, 512, 1024, 2048, 4096, 8192),
                   count=256, width=2048, inner=768, top_k=8, steps=8,
                   repeats=5):
    """The routed experts' products of ONE expert layer through both
    tilings (``ops/moe.streamed_experts``: each touched expert once,
    its matrices whole; ``ops/moe.grouped_experts``:
    ``jax.lax.ragged_dot``) at each number of rows (assignments:
    tokens x ``top_k``, every token ``top_k`` distinct experts chosen
    uniformly), at the benchmark's expert model's published widths by
    default: what ``ops/moe.STREAM_MAX_ROWS`` is read from (PERF.md
    §5). Only the TPU gives times worth a name:
    ``chiprun -- python -c "import bench, json;
    print(json.dumps(bench.moe_rows_sweep()))"``.

    Per row count: the touched experts, ``streamed_ms`` and
    ``grouped_ms`` a call (median of ``repeats`` timings of ``steps``
    calls chained inside one program, each call's rows moved by the
    one before so that none is hoisted), the gigabytes a second the
    touched experts' matrices alone make of the streamed time, and the
    widest gap between the two results (the gate is float32 in the
    one, the rows' type in the other)."""
    from jax import lax

    from veles_tpu.ops import moe

    rng = numpy.random.RandomState(3)
    keys = jax.random.split(jax.random.key(7), 3)

    def leaf(key, a, b):
        return (jax.random.normal(key, (count, a, b), jnp.float32)
                / math.sqrt(a)).astype(jnp.bfloat16)

    experts = {"w_gate": leaf(keys[0], width, inner),
               "w_up": leaf(keys[1], width, inner),
               "w_down": leaf(keys[2], inner, width)}

    def chained(products):
        def run(x, load, experts):
            def step(x, _):
                out = products(x, load, experts)
                return x + (out * 1e-3).astype(x.dtype), None
            return lax.scan(step, x, None, length=steps)[0]
        return jax.jit(run)

    def streamed(x, load, experts):
        return moe.streamed_experts(
            x, moe.visit_table(load, x.shape[0]), experts)

    def median_ms(fn, args):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append((time.perf_counter() - t0) / steps)
        return 1e3 * float(numpy.median(times))

    out = {"device": device_info(), "count": count, "width": width,
           "inner": inner, "top_k": top_k, "rows": []}
    matrices = 3 * width * inner * 2
    for n in rows:
        chosen = numpy.argsort(-rng.rand(n // top_k, count), -1)[:, :top_k]
        load = jnp.asarray(numpy.bincount(chosen.ravel(),
                                          minlength=count), jnp.int32)
        x = jnp.asarray(rng.randn(n, width), jnp.bfloat16)
        touched = int((load > 0).sum())
        line = {"rows": n, "touched": touched}
        line["grouped_ms"] = round(median_ms(
            chained(moe.grouped_experts), (x, load, experts)), 4)
        line["streamed_ms"] = round(median_ms(
            chained(streamed), (x, load, experts)), 4)
        line["streamed_gb_per_s"] = round(
            touched * matrices / line["streamed_ms"] / 1e6, 1)
        line["gap"] = float(jnp.abs(
            streamed(x, load, experts)
            - moe.grouped_experts(x, load, experts)).max())
        out["rows"].append(line)
    return out


def reshard_section(blocks=2, embed=256, heads=8, vocab=2048,
                    slots=4, budget=24, chunk=8, repeats=5):
    """The train↔serve layout transition, measured (ROADMAP item 1 /
    docs/sharded_serving.md): one transformer checkpoint moves between
    the fused train layout (params replicated over the mesh — the
    data-parallel tick's P() spec) and the slot-serving layout (params
    tensor-parallel on ``model``, per ``decode.slot_param_specs``)
    through ``parallel/reshard.py``'s collective schedules, both
    directions, against the naive ``device_put`` formulation on the
    same tree. Plus the sharded slot engine's decode step time — the
    tensor-parallel continuous-batching path finally gets a bench
    number beside the single-chip ``decode_continuous_*`` family.

    Requires >= 2 devices (the bench driver falls back to an 8-device
    virtual-CPU subprocess via :func:`reshard_bench`); keys:

    - ``reshard_train_to_serve_ms`` / ``reshard_serve_to_train_ms``
      (min-of-``repeats`` wall, compile excluded) + ``_bytes`` each and
      the combined ``reshard_bytes`` (lower is better — the schedule's
      bytes-on-the-wire, registered direction-aware in
      ``observe/regress.py``);
    - ``reshard_naive_*_ms``: the ``device_put`` baseline;
    - ``decode_continuous_sharded_step_ms`` / ``_tokens_per_sec``: the
      sharded slot engine draining a staggered request mix.
    """
    from veles_tpu.parallel import reshard as rs
    from veles_tpu.parallel.decode import slot_param_specs
    from veles_tpu.parallel.mesh import build_mesh
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import ContinuousDecoder
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()
    if len(devices) < 2:
        return None
    n = len(devices)
    while heads % n or vocab % n:
        n -= 1
    mesh = build_mesh(devices=devices[:n], data=1, model=n)
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.02)
    serve_specs = slot_param_specs(params)
    train_specs = P()  # the fused tick's replicated-params layout
    # place the checkpoint in the train layout once; the measured
    # transitions then start and end ON the mesh
    train_tree, _ = rs.reshard(params, mesh, train_specs,
                               label="bench.place")
    out = {}
    transitions = (
        ("reshard_train_to_serve", train_tree, serve_specs),
        ("reshard_serve_to_train",
         rs.reshard(train_tree, mesh, serve_specs,
                    label="bench.warm")[0], train_specs),
    )
    total_bytes = 0
    for key, src_tree, dst_specs in transitions:
        times = []
        stats = None
        for _ in range(repeats + 1):  # first call compiles
            _, stats = rs.reshard(src_tree, mesh, dst_specs,
                                  label=key)
            times.append(stats["seconds"])
        times = sorted(times[1:])
        out[key + "_ms"] = round(times[0] * 1000, 3)
        out[key + "_spread"] = round((times[1] - times[0])
                                     / max(times[0], 1e-9), 4)
        out[key + "_bytes"] = stats["bytes"]
        total_bytes += stats["bytes"]
        naive = min(rs.naive_reshard(src_tree, mesh, dst_specs)[1]
                    for _ in range(repeats))
        out[key.replace("reshard_", "reshard_naive_") + "_ms"] = \
            round(naive * 1000, 3)
    out["reshard_bytes"] = total_bytes
    out["reshard_config"] = "model%d_L%d_e%d_h%d_v%d" % (
        n, blocks, embed, heads, vocab)

    # sharded continuous decode: the same staggered-drain recipe as
    # decode_continuous, on the tensor-parallel slot engine
    prompts = [rng.randint(0, vocab, p) for p in (24, 48, 32, 40, 28,
                                                  36, 44, 20)]

    def run():
        dec = ContinuousDecoder(params, table, heads, slots=slots,
                                max_len=64 + budget + 2 * chunk,
                                n_tokens=budget, mesh=mesh)
        pending = list(prompts)
        for _ in range(min(slots, len(pending))):
            dec.submit(pending.pop())
        t0 = time.perf_counter()
        dec.drain_pipelined(
            chunk, admit=lambda: pending and dec.submit(pending.pop()))
        dt = time.perf_counter() - t0
        step_s = ((dec.timings["dispatch_s"] + dec.timings["collect_s"])
                  / max(dec.steps, 1))
        return dec.tokens_out / dt, step_s

    run()  # compile the sharded admit + chunk programs
    runs = [run() for _ in range(2)]
    best_rate, step_s = max(runs, key=lambda r: r[0])
    out["decode_continuous_sharded_step_ms"] = round(step_s * 1000, 3)
    out["decode_continuous_sharded_tokens_per_sec"] = round(best_rate, 1)
    out["decode_continuous_sharded_spread"] = round(
        (best_rate - min(r[0] for r in runs)) / best_rate, 4)
    out["decode_continuous_sharded_config"] = \
        "model%d_s%d_b%d_c%d_L%d_e%d_h%d_v%d" % (
            n, slots, budget, chunk, blocks, embed, heads, vocab)
    return out


def reshard_bench():
    """``reshard_section`` keys, wherever the bench runs: in-process on
    a multi-device backend; on a single-chip device via an 8-device virtual-CPU subprocess — the transition
    schedule and its byte accounting are device-count facts, so the CPU
    mesh records honest bytes and CI-comparable latencies (the same
    doctrine as ``pod_cpu8_tick_ms``)."""
    import subprocess
    import sys

    if len(jax.devices()) >= 2:
        return reshard_section()
    child = ("import json, bench\n"
             "print(json.dumps(bench.reshard_section()))\n")
    proc = subprocess.run([sys.executable, "-c", child], env=_cpu8_env(),
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        print(proc.stderr[-2000:], file=sys.stderr)
        return {}
    keys = json.loads(proc.stdout.strip().splitlines()[-1])
    if not keys:
        return {}
    keys["reshard_config"] = keys.get("reshard_config", "") + "_cpu8"
    return keys


#: one geometry for the cold-start twins (parent builds the bundle,
#: both children rebuild the same params from the seed) — serving-bench
#: scale, small enough that the live child's trace+compile finishes in
#: CI time
COLDSTART_CFG = dict(blocks=2, embed=256, heads=8, vocab=2048, slots=4,
                     max_len=256, n_tokens=16, chunk=8, seed=0)


def coldstart_child(kind, bundle=None, cfg=None):
    """One cold-start step, run in a FRESH subprocess on the CPU
    platform (a warm parent cannot honestly measure cold start, and
    the children must share one device fingerprint with the bundle —
    the CPU-child doctrine of ``reshard_bench``/``fleet_bench``, so
    the keys stay CI-comparable wherever the bench runs):
    ``kind="build"`` writes the bundle; ``kind="live"`` boots a
    serving decoder by tracing + compiling, ``kind="aot"`` by loading
    the bundle, ``kind="cached"`` by loading it through the persistent
    executable cache (the sibling ``<bundle>.xcache/`` —
    docs/zero_downtime.md) — time to the first generated chunk, then a
    warmup over every prompt bucket, then the XLA compile tally the
    decode programs booked (``observe/xla_stats``). Prints one JSON
    line; the AOT child's ``compiles == 0`` is the device-truth
    zero-retrace proof the regression sentinel pins, and the cached
    child's ``aot.compiled_live == 0`` is the cache-hit proof."""
    import time

    cfg = dict(COLDSTART_CFG, **(cfg or {}))
    import numpy

    from veles_tpu.observe.xla_stats import get_compile_tracker
    from veles_tpu.parallel.transformer_step import \
        init_transformer_params
    from veles_tpu.serving import ContinuousDecoder

    tracker = get_compile_tracker()
    tracker.enable()
    rng = numpy.random.RandomState(cfg["seed"])
    params = init_transformer_params(rng, cfg["blocks"], cfg["embed"],
                                     cfg["heads"], cfg["vocab"])
    table = jnp.asarray(rng.randn(cfg["vocab"], cfg["embed"])
                        .astype(numpy.float32) * 0.3)
    if kind == "build":
        from veles_tpu.aot.artifact import build_serving_bundle
        t0 = time.perf_counter()
        build_serving_bundle(params, table, cfg["heads"], bundle,
                             slots=cfg["slots"],
                             max_len=cfg["max_len"],
                             n_tokens=cfg["n_tokens"],
                             chunk=cfg["chunk"])
        out = {"build_ms": round(
            (time.perf_counter() - t0) * 1000.0, 1),
            "bytes": os.path.getsize(bundle)}
        print(json.dumps(out))
        return out
    if kind == "warm":
        # `veles_tpu aot warm-cache`'s path: compile EVERY program
        # synchronously and persist the executables, so the cached
        # twin measures a fully-warm boot (a serving boot's lazy
        # prefetch can exit before the tail of the bundle is stored)
        from veles_tpu.aot.loader import load_bundle
        t0 = time.perf_counter()
        programs = load_bundle(bundle, eager=True, prefetch=False,
                               exec_cache=True)
        out = dict(programs.stats(),
                   warm_ms=round((time.perf_counter() - t0) * 1000.0,
                                 1))
        print(json.dumps(out))
        return out
    prompt = rng.randint(0, cfg["vocab"], 12)
    t0 = time.perf_counter()
    aot = None
    if kind in ("aot", "cached"):
        from veles_tpu.aot.loader import load_bundle
        aot = load_bundle(bundle, exec_cache=(kind == "cached"))
    dec = ContinuousDecoder(params, table, cfg["heads"],
                            slots=cfg["slots"], max_len=cfg["max_len"],
                            n_tokens=cfg["n_tokens"], aot=aot)
    rid = dec.submit(prompt)
    while not dec.results.get(rid):
        dec.step_many(cfg["chunk"])
    first_token_ms = (time.perf_counter() - t0) * 1000.0
    # warmup: one prompt per bucket the decoder serves, so every admit
    # shape the replica will ever compile is exercised
    bucket = 16
    while bucket <= cfg["max_len"]:
        n = max(1, min(bucket - 1,
                       cfg["max_len"] - cfg["n_tokens"] - 1))
        dec.submit(rng.randint(0, cfg["vocab"], n))
        bucket *= 2
    dec.run_until_drained(chunk=cfg["chunk"])
    snap = tracker.snapshot()
    compiles = sum(count for name, count in snap["compiles"].items()
                   if name.startswith(("decode.", "paged.")))
    out = {"first_token_ms": round(first_token_ms, 1),
           "compiles": compiles,
           "tokens": dec.tokens_out}
    if aot is not None:
        out["aot"] = aot.stats()
    print(json.dumps(out))
    return out


def coldstart_section(repeats=2):
    """Cold-start-to-first-token, live-compile vs AOT-load (ROADMAP
    item 4 / docs/aot_artifacts.md): a fresh CPU subprocess builds the
    serving bundle (`veles_tpu aot build`'s path — in a CHILD so the
    bundle's device fingerprint matches the twins' platform even when
    the bench parent runs on a TPU), then fresh subprocess twins boot
    a decoder each way. Records the measured
    ``coldstart_to_first_token_ms`` (AOT) against the live twin, and
    ``coldstart_compiles`` — the AOT warmup's live-compile tally,
    pinned 0 by the device-truth counter (lower-better in
    ``observe/regress``)."""
    import subprocess
    import sys
    import tempfile

    cfg = COLDSTART_CFG
    tmp = tempfile.mkdtemp(prefix="veles_aot_bench_")
    bundle = os.path.join(tmp, "coldstart.aot.tar")

    env = _cpu8_env()
    env["XLA_FLAGS"] = ""  # cold start is a single-replica fact

    def child(kind, runs=repeats):
        code = ("import bench\n"
                "bench.coldstart_child(%r, bundle=%r)\n"
                % (kind, bundle))
        best = None
        for _ in range(runs):
            proc = subprocess.run([sys.executable, "-c", code],
                                  env=env, capture_output=True,
                                  text=True, timeout=900)
            if proc.returncode != 0:
                print(proc.stderr[-2000:], file=sys.stderr)
                return None
            row = json.loads(proc.stdout.strip().splitlines()[-1])
            if best is None or row.get("first_token_ms", 0) \
                    < best.get("first_token_ms", 0):
                best = row
        return best

    built = child("build", runs=1)
    if not built:
        return {}
    build_ms = built["build_ms"]
    live = child("live")
    aot = child("aot")
    if not live or not aot:
        return {}
    # persistent executable cache (docs/zero_downtime.md): the
    # warm-cache pass compiles + persists every program into the
    # sibling <bundle>.xcache/, then fresh twins measure the cached
    # boot — every decode program must come from the cache
    # (compiled_live pinned 0; the regress sentinel watches the _ms
    # key).
    cached = None
    if child("warm", runs=1) is not None:
        cached = child("cached")
    out = {
        "coldstart_live_to_first_token_ms": live["first_token_ms"],
        "coldstart_to_first_token_ms": aot["first_token_ms"],
        "coldstart_first_token_speedup": round(
            live["first_token_ms"] / aot["first_token_ms"], 2),
        "coldstart_live_compiles": live["compiles"],
        "coldstart_compiles": aot["compiles"],
        "coldstart_bundle_build_ms": round(build_ms, 1),
        "coldstart_bundle_bytes": os.path.getsize(bundle),
        "coldstart_aot_programs": (aot.get("aot") or {}).get(
            "programs"),
        "coldstart_config": "blocks%d_embed%d_slots%d_maxlen%d_cpu"
                            % (cfg["blocks"], cfg["embed"],
                               cfg["slots"], cfg["max_len"]),
    }
    if cached:
        stats = cached.get("aot") or {}
        xc = stats.get("exec_cache") or {}
        out.update({
            "coldstart_cached_to_first_token_ms":
                cached["first_token_ms"],
            "coldstart_cached_compiles": cached["compiles"],
            "coldstart_cached_from_cache": stats.get("from_cache"),
            "coldstart_cached_compiled_live": stats.get(
                "compiled_live"),
            "coldstart_cached_hits": xc.get("hits"),
            "coldstart_cached_rejects": xc.get("rejects"),
        })
    return out


def fleet_section(in_f=784, hidden=1024, classes=10, batch=1024,
                  repeats=12):
    """In-program fleet aggregation vs the measured host-aggregation
    baseline (ROADMAP item 3 / docs/compiler_fleet.md), same gradient
    tree, same device count. Requires >= 2 devices (the driver falls
    back to the 8-device virtual-CPU subprocess via
    :func:`fleet_bench`); keys:

    - ``fleet_reduce_ms`` / ``fleet_reduce_bytes``: one in-program
      all-reduce of the 2-layer MLP gradient tree over the full mesh
      (f32 tier == the product-default psum; min-of-``repeats`` wall,
      compile excluded) and its analytic wire bytes; ``_bf16_`` /
      ``_int8_`` twins for the compressed tiers;
    - ``fleet_host_baseline_ms``: the SAME tree through the data-plane
      host path one update takes — device→host, fleet-protocol frame
      encode (pickle+gzip, ``fleet/protocol.py``), decode, host→device,
      merge under the update-lock semantics — the per-step cost the
      control-plane refit deletes;
    - ``fleet_inprogram_speedup``: baseline / in-program (must stay
      strictly > 1 — the acceptance bar);
    - ``fleet_step_ms`` / ``fleet_step_mfu``: the full
      ``mapreduce.fleet_train_step`` (fused forward+backward+reduce+
      update as ONE program) per-step wall and its MFU from
      ``observe/xla_stats`` cost analysis (on the CPU-8 fallback the
      peak is a pinned nominal 1.0 TFLOP/s so the ratio is a stable
      regression number, not a hardware claim — ``fleet_config`` says
      which).
    """
    from veles_tpu.core.config import root
    from veles_tpu.fleet.protocol import decode_frame_bytes, encode_frame
    from veles_tpu.observe import xla_stats
    from veles_tpu.parallel import mapreduce as mr
    from veles_tpu.parallel.mesh import build_mesh, shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    if n < 2:
        return None
    mesh = build_mesh(devices=devices, data=n)
    rng = numpy.random.RandomState(0)
    grads = {"w1": rng.randn(n, in_f, hidden).astype(numpy.float32),
             "b1": rng.randn(n, hidden).astype(numpy.float32),
             "w2": rng.randn(n, hidden, classes).astype(numpy.float32),
             "b2": rng.randn(n, classes).astype(numpy.float32)}
    sharded = jax.device_put(
        grads, NamedSharding(mesh, P("data")))
    one_replica = jax.tree.map(lambda x: x[0], grads)

    out = {}
    for tier in ("f32", "bf16", "int8"):
        def body(t, tier=tier):
            local = jax.tree.map(lambda x: x[0], t)
            return mr.reduce_sum(local, "data", precision=tier)
        fn = jax.jit(shard_map(body, mesh=mesh,
                               in_specs=(P("data"),), out_specs=P()))
        jax.block_until_ready(fn(sharded))  # compile + warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(sharded))
            times.append(time.perf_counter() - t0)
        times.sort()
        suffix = "" if tier == "f32" else "_" + tier
        out["fleet_reduce%s_ms" % suffix] = round(times[0] * 1000, 3)
        out["fleet_reduce%s_spread" % suffix] = round(
            (times[1] - times[0]) / max(times[0], 1e-9), 4)
        out["fleet_reduce%s_bytes" % suffix] = mr.reduce_wire_bytes(
            one_replica, n, tier)

    # the measured host-aggregation baseline: what ONE data-plane
    # update costs the master per step on the same tree — the exact
    # device→frame→device→merge path fleet/server.py ran before the
    # control-plane refit
    key = b"bench-fleet"
    device_tree = jax.device_put(one_replica)
    master_tree = jax.device_put(one_replica)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        host = jax.device_get(device_tree)            # slave: .mem
        frame = encode_frame({"type": "update", "update": host}, key)
        update = decode_frame_bytes(frame, key)["update"]  # master
        merged = jax.tree.map(                        # _locked_apply
            lambda cur, new: (cur + jnp.asarray(new)) * 0.5,
            master_tree, update)
        jax.block_until_ready(merged)
        times.append(time.perf_counter() - t0)
    times.sort()
    out["fleet_host_baseline_ms"] = round(times[0] * 1000, 3)
    out["fleet_host_baseline_spread"] = round(
        (times[1] - times[0]) / max(times[0], 1e-9), 4)
    out["fleet_inprogram_speedup"] = round(
        out["fleet_host_baseline_ms"] / max(out["fleet_reduce_ms"],
                                            1e-9), 2)

    # the full in-program fleet step, MFU from cost analysis: a dense
    # 2-layer tick through mapreduce.fleet_train_step (the product
    # path the control-plane slave runs)
    tracker = xla_stats.get_compile_tracker()
    was_enabled = tracker.enabled
    tracker.enabled = True
    nominal_peak = False
    if xla_stats.peak_tflops() is None:
        # CPU fallback: pin a nominal denominator so the ratio is a
        # stable regression number (fleet_config records the pin)
        root.common.observe.peak_tflops = 1.0
        nominal_peak = True
    try:
        specs = [
            {"kind": "dense", "activation": "tanh",
             "leaves": (("w", "weights", "_velocity_w", False, True),
                        ("b", "bias", "_velocity_b", True, False)),
             "has_params": True, "solver": "momentum"},
            {"kind": "dense", "activation": "linear",
             "leaves": (("w", "weights", "_velocity_w", False, True),
                        ("b", "bias", "_velocity_b", True, False)),
             "has_params": True, "solver": "momentum"},
        ]
        steps = mr.fleet_train_step(mesh, specs, "none",
                                    with_confusion=False,
                                    reduce_precision="f32")
        train_step = steps[0]
        params = []
        fan = in_f
        for width in (hidden, classes):
            w = jnp.asarray(rng.randn(fan, width)
                            .astype(numpy.float32) * 0.05)
            params.append({"p": {"w": w,
                                 "b": jnp.zeros(width, jnp.float32)},
                           "v": {"w": jnp.zeros_like(w),
                                 "b": jnp.zeros(width, jnp.float32)}})
            fan = width
        hyper = jnp.asarray([0.03, 0.03, 0.0, 0.0, 0.9, 0.9, 0.999,
                             1e-8], jnp.float32)
        hypers = [hyper, hyper]
        data = jnp.asarray(rng.rand(batch, in_f)
                           .astype(numpy.float32))
        labels = jnp.asarray(rng.randint(0, classes, batch))
        indices = jnp.arange(batch, dtype=jnp.int64)
        valid = numpy.float32(batch)
        seed = numpy.int64(0)
        params, metrics = train_step(params, hypers, {}, data, labels,
                                     indices, valid, seed)
        jax.block_until_ready(metrics)  # compile + warm
        step_times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            params, metrics = train_step(params, hypers, {}, data,
                                         labels, indices, valid, seed)
            jax.block_until_ready(metrics)
            dt = time.perf_counter() - t0
            step_times.append(dt)
            # no manual observe_step here: the fleet_train_step
            # wrapper already feeds the MFU EMA with its call cadence
            # (== the blocked wall in this loop)
        step_times.sort()
        out["fleet_step_ms"] = round(step_times[0] * 1000, 3)
        out["fleet_step_spread"] = round(
            (step_times[1] - step_times[0])
            / max(step_times[0], 1e-9), 4)
        mfu = tracker.snapshot()["mfu"].get("mapreduce.fleet_train_step",
                                            {})
        if mfu.get("mfu") is not None:
            out["fleet_step_mfu"] = round(mfu["mfu"], 4)
    finally:
        tracker.enabled = was_enabled
        if nominal_peak:
            root.common.observe.peak_tflops = None
    out["fleet_config"] = "data%d_i%d_h%d_c%d_b%d%s" % (
        n, in_f, hidden, classes, batch,
        "_nominal_peak1" if nominal_peak else "")
    return out


def fleet_bench():
    """``fleet_section`` keys wherever the bench runs: in-process on a
    multi-device backend, else via the 8-device virtual-CPU subprocess
    (the ``reshard_bench`` doctrine — collective cost and wire bytes
    are device-count facts the CPU mesh measures honestly)."""
    import subprocess
    import sys

    if len(jax.devices()) >= 2:
        return fleet_section()
    child = ("import json, bench\n"
             "print(json.dumps(bench.fleet_section()))\n")
    proc = subprocess.run([sys.executable, "-c", child], env=_cpu8_env(),
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        print(proc.stderr[-2000:], file=sys.stderr)
        return {}
    keys = json.loads(proc.stdout.strip().splitlines()[-1])
    if not keys:
        return {}
    keys["fleet_config"] = keys.get("fleet_config", "") + "_cpu8"
    return keys


class _ObservatoryWorkflow:
    """Minimal fleet-protocol workflow for :func:`fleetscope_section`:
    the master side serves ``jobs`` integers, the slave side burns a
    fixed busy-compute window per job — a real wire, real stamps, real
    goodput accounting, no model in the way."""

    checksum = "fleetscope-bench"

    def __init__(self, jobs=(), job_busy_s=0.0):
        self._jobs = list(jobs)
        self.job_busy_s = job_busy_s
        self.applied = []

    def generate_initial_data_for_slave(self, slave):
        return None

    def generate_data_for_slave(self, slave):
        return self._jobs.pop(0) if self._jobs else None

    def apply_data_from_slave(self, update, slave):
        self.applied.append(update)

    def apply_initial_data_from_master(self, initial):
        pass

    def do_job(self, job, callback):
        # sleep, not a busy spin: both slaves share one process (and
        # one GIL) in the loopback bench — a spin would smear every
        # other thread's measured residence
        time.sleep(self.job_busy_s)
        callback({"job": job})

    def drop_slave(self, slave):
        pass

    def has_more_jobs(self):
        return bool(self._jobs)


def _observatory_fleet(n_jobs, busy_s, slow_factor=1.0, timeout=60.0,
                       watch_straggler=False):
    """One loopback master + two slaves; returns ``(master,
    detect_ms)`` after the job stream drains — ``detect_ms`` is the
    wall from fleet start to the straggler detector first naming a
    slave (polled DURING the run; None when it never fired or
    ``watch_straggler`` is off)."""
    from veles_tpu.fleet.client import Client
    from veles_tpu.fleet.server import Server

    master = Server("127.0.0.1:0",
                    _ObservatoryWorkflow(jobs=range(n_jobs)),
                    secret="fleetscope-bench")
    done = {"flag": False}
    master.on_finished = lambda: done.update(flag=True)
    master.start()
    start = time.perf_counter()
    clients = []
    for index in range(2):
        busy = busy_s * (slow_factor if index == 1 else 1.0)
        client = Client("127.0.0.1:%d" % master.port,
                        _ObservatoryWorkflow(job_busy_s=busy),
                        secret="fleetscope-bench", chaos=False)
        clients.append(client.start())
    detect_at = None
    deadline = start + timeout
    while not done["flag"] and time.perf_counter() < deadline:
        if watch_straggler and detect_at is None \
                and master.scope.straggler_summary() is not None:
            detect_at = time.perf_counter()
        time.sleep(0.005)
    master.drain(timeout=5.0)
    for client in clients:
        client.stop()
    detect_ms = (None if detect_at is None
                 else (detect_at - start) * 1e3)
    return master, detect_ms


def fleetscope_section():
    """The fleet goodput observatory section (observe/fleetscope.py;
    docs/observability.md "Fleet timeline + goodput"); keys:

    - ``fleet_span_ship_overhead_ns``: record-path cost of one
      completed-span summary landing in the slave's bounded ring
      (lower is better — the flight-recorder overhead contract);
    - ``fleet_goodput_fraction``: measured compute share of fleet wall
      on a balanced two-slave loopback fleet (higher is better);
    - ``fleet_straggler_detect_ms``: wall time from the straggler
      fleet's first job until the detector names the slow slave
      (lower is better)."""
    from veles_tpu.observe.fleetscope import SpanRing

    out = {"fleetscope_config": "loopback-2slaves"}
    ring = SpanRing(capacity=512)
    ring.enable()
    best = None
    for _ in range(3):
        n = 20000
        start = time.perf_counter()
        for index in range(n):
            ring.note_span("bench.span", "trace", "span%d" % index,
                           None, 0.0, 1.0, 0)
        per_note = (time.perf_counter() - start) / n * 1e9
        best = per_note if best is None else min(best, per_note)
    out["fleet_span_ship_overhead_ns"] = round(best, 1)
    # balanced fleet: the goodput fraction of a healthy wire
    master, _ = _observatory_fleet(n_jobs=24, busy_s=0.004)
    try:
        goodput = master.scope.goodput_summary(
            wasted_s=master.ledger.snapshot().get("wasted_s", 0.0))
        out["fleet_goodput_fraction"] = goodput["fraction"]
        out["fleet_goodput_jobs"] = goodput["jobs"]
    finally:
        master.stop()
    # straggler fleet: slave #2 sleeps 6x per job; detection latency
    # is polled DURING the run (fleet start -> detector names it)
    master, detect_ms = _observatory_fleet(
        n_jobs=80, busy_s=0.003, slow_factor=6.0,
        watch_straggler=True)
    try:
        straggler = master.scope.straggler_summary()
        if detect_ms is not None and straggler is not None:
            out["fleet_straggler_detect_ms"] = round(detect_ms, 1)
            out["fleet_straggler_slave"] = straggler["slave"]
    finally:
        master.stop()
    return out


def servescope_section(embed=128, heads=4, blocks=2, vocab=512,
                       slots=4, budget=16, chunk=4):
    """The serving goodput observatory section
    (observe/servescope.py; docs/observability.md "Serving goodput +
    slot timeline"); keys:

    - ``serve_scope_note_ns``: record-path cost of one per-dispatch
      accounting note (lower is better — the flight-recorder overhead
      contract);
    - ``serve_goodput_fraction``: useful share of dispatched tokens
      on a staggered mixed-length continuous-batching drain (higher
      is better);
    - ``serve_waste_share`` + per-cause ``serve_<cause>_waste_share``:
      the waste decomposition of the same run (all lower-better under
      ``make regress``);
    - ``serve_slot_occupancy_fraction``: live share of decode
      lane-steps (higher is better)."""
    from veles_tpu.observe.servescope import ServeScope, \
        get_serve_scope
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import ContinuousDecoder

    out = {"servescope_config": "s%d_b%d_c%d_e%d_h%d_L%d_v%d"
                                % (slots, budget, chunk, embed, heads,
                                   blocks, vocab)}
    # record-path overhead: one dispatch note on a throwaway scope
    probe = ServeScope()
    best = None
    for _ in range(3):
        n = 20000
        start = time.perf_counter()
        for _ in range(n):
            probe.note_dispatch(4, 8, 6, 12, 0.0)
        per_note = (time.perf_counter() - start) / n * 1e9
        best = per_note if best is None else min(best, per_note)
    out["serve_scope_note_ns"] = round(best, 1)
    # the measured decomposition: a staggered mixed-length drain on
    # the PROCESS scope (reset first — the bench owns this process),
    # so buckets/groups/span tiles/dead slots all contribute
    scope = get_serve_scope()
    scope.reset()
    rng = numpy.random.RandomState(0)
    params = init_transformer_params(rng, blocks, embed, heads, vocab)
    table = jnp.asarray(
        rng.randn(vocab, embed).astype(numpy.float32) * 0.02)
    dec = ContinuousDecoder(params, table, heads, slots=slots,
                            max_len=256, n_tokens=budget)
    pending = [rng.randint(0, vocab, n).tolist()
               for n in (24, 40, 72, 100, 24, 56, 88, 33)]
    for _ in range(min(slots, len(pending))):
        dec.submit(pending.pop())

    def admit():
        if pending:
            dec.submit(pending.pop())

    dec.drain_pipelined(chunk, admit=admit)
    goodput = scope.goodput_summary()
    out["serve_goodput_fraction"] = goodput["fraction"]
    total = goodput["useful_tokens"] + goodput["waste_tokens"]
    if total:
        out["serve_waste_share"] = round(
            goodput["waste_tokens"] / total, 4)
        for cause, tokens in sorted(scope.waste.items()):
            out["serve_%s_waste_share" % cause] = round(tokens / total,
                                                        4)
    occupancy = scope.occupancy()["fraction"]
    if occupancy is not None:
        out["serve_slot_occupancy_fraction"] = occupancy
    return out


def _guarded(fn, *args, fallback=(None, []), **kwargs):
    """One failed section must not kill the headline line — but the
    failure has to be visible somewhere (stderr; stdout stays one JSON
    line)."""
    try:
        return fn(*args, **kwargs)
    except Exception:
        import traceback
        traceback.print_exc()
        return fallback


#: default incremental-artifact path (override with --artifact PATH);
#: every completed section lands here atomically, so a killed run or a
#: truncated stdout capture never loses measured keys again
#: (observe/regress.py)
ARTIFACT_PATH = "BENCH_artifact.json"


def _spread_warns(keys, threshold=0.1):
    """The noisy-keys satellite's tripwire: a ``<key>_warn: true`` flag
    beside every ``*_spread`` above ``threshold``, so a round whose
    timers went unstable says so ON the artifact instead of leaving a
    reviewer to eyeball 40 spread values."""
    return {key + "_warn": True for key, value in keys.items()
            if key.endswith("_spread") and not isinstance(value, bool)
            and isinstance(value, (int, float)) and value > threshold}


def _make_artifact(path=None):
    from veles_tpu.observe.regress import BenchArtifact
    return BenchArtifact(path or ARTIFACT_PATH)


def main(artifact_path=None):
    artifact = _make_artifact(artifact_path)
    kind, peak = device_info()
    artifact.update({"device_kind": kind, "peak_bf16_tflops": peak})
    data, labels = _dataset()
    # headline: TWO full measured runs; the claimed value is the best
    # run's mean-epoch rate and the spread is the run-to-run gap — the
    # reproducibility of the CLAIMED number (per-epoch intervals under
    # the pipelined engine are bursty by design: the host enqueues
    # ahead, the drain epoch pays it back, so their rel-std measured
    # noise, not instability)
    runs = [workflow_throughput(True, data, labels, epochs=5)
            for _ in range(2)]
    (fused_ips, fused_deltas) = max(runs, key=lambda r: r[0])
    headline_spread = round(
        (fused_ips - min(r[0] for r in runs)) / fused_ips, 4)
    artifact.update({
        "mnist784_workflow_train_throughput": round(fused_ips, 1),
        "headline_run_spread": headline_spread})
    cliff = cliff_family(data, labels)
    graph_ips, graph_spread = cliff["graph"]
    partial_ips, partial_spread = cliff["segment"]
    sweep_ips, sweep_spread = cliff["sweep"]
    tx_tps, _ = _guarded(transformer_throughput)
    device_keys = {}

    def _add(section):
        # each completed section persists IMMEDIATELY (atomic temp +
        # os.replace): a crash or truncated capture past this point
        # cannot lose it
        device_keys.update(section)
        artifact.update(section)

    _add(_guarded(fused_step_device, peak, fallback={}))
    alexnet_ips, alex_epoch_ips, alex_wf = _guarded(
        alexnet_throughput, fallback=(None, [], None))
    if alex_wf is not None and alex_wf.fused_tick is not None:
        _add(_guarded(alexnet_device, alex_wf, peak, fallback={}))
        big = _guarded(alexnet_device, alex_wf, peak, minibatch=512,
                       fallback={})
        _add({"alexnet_mfu_device_mb512": big.get("alexnet_mfu_device")})
    # drop the AlexNet workflow (1.85 GB device-resident dataset +
    # params): keeping it alive through the decode sections fragments
    # HBM and their repeat timings turn noisy (spread 0.3 vs 0.003
    # measured in a fresh process)
    alex_wf = None
    _add(_guarded(transformer_device, peak, fallback={}))
    _add(_guarded(longctx_device, fallback={}))
    _add(_guarded(decode_device, fallback={}))
    _add(_guarded(decode_device, dtype=jnp.bfloat16, fallback={}))
    _add(_guarded(decode_int8_device, fallback={}))
    _add(_guarded(decode_int8_device, kv_quant=True, fallback={}))
    _add(_guarded(decode_continuous, fallback={}))
    _add(_guarded(reshard_bench, fallback={}))
    _add(_guarded(fleet_bench, fallback={}))
    _add(_guarded(fleetscope_section, fallback={}))
    _add(_guarded(servescope_section, fallback={}))
    _add(_guarded(coldstart_section, fallback={}))
    _add(_guarded(pod_overhead, fallback={}))
    _add(_guarded(pallas_epilogue_compare, fallback={}))
    gflops = device_keys.get("fused_step_gflops")
    titan_gflops = 2 * 3001 ** 3 / 0.1642 / 1e9  # reference GEMM anchor
    epoch_mean, epoch_std = _mean_std(fused_deltas)
    alex_gflops = (ALEXNET_TRAIN_GFLOP_PER_IMAGE * alexnet_ips
                   if alexnet_ips else None)
    out = {
        "metric": "mnist784_workflow_train_throughput",
        "value": round(fused_ips, 1),
        "unit": "images/sec/chip",
        "vs_baseline": (round(fused_ips / graph_ips, 2)
                        if graph_ips else None),
        # -- measurement context (honest accounting) ----
        "device_kind": kind,
        "peak_bf16_tflops": peak,
        "epochs_measured": len(fused_deltas),
        "epoch_sec_mean": round(epoch_mean, 4),
        "epoch_sec_std": round(epoch_std, 4),
        # reproducibility of the CLAIMED value: relative gap between
        # the two full measured runs (epoch-interval rel-std measured
        # pipelining burstiness, not run instability)
        "headline_run_spread": headline_spread,
        # -- the cliff family (interleaved, common estimator) ----------
        "graph_mode_images_per_sec":
            round(graph_ips, 1) if graph_ips else None,
        "graph_mode_spread": graph_spread,
        "graph_mode_partial_fused_images_per_sec":
            round(partial_ips, 1) if partial_ips else None,
        "partial_fused_spread": partial_spread,
        # SAME workflow, host unit declared sweep-transparent: the
        # sweep tier scans it per class sweep (on/off)
        "sweep_tier_images_per_sec":
            round(sweep_ips, 1) if sweep_ips else None,
        "sweep_tier_spread": sweep_spread,
        # -- utilization (device-time derived: *_device_* keys come
        # from two-length scan timing, per-call constants cancelled) --
        "fused_step_vs_titan_gemm": (round(gflops / titan_gflops, 2)
                                     if gflops else None),
        # K40-era Caffe AlexNet was ~450 img/s; BASELINE asks >=2x
        "alexnet227_images_per_sec":
            round(alexnet_ips, 1) if alexnet_ips else None,
        "alexnet227_ips_std": (
            round(_mean_std(alex_epoch_ips)[1], 1)
            if alex_epoch_ips else None),
        # wall-clock MFU through the workflow loop (dispatch-capped);
        # alexnet_mfu_device is the honest device number
        "alexnet_mfu": _mfu(alex_gflops, peak),
        "transformer_tokens_per_sec":
            round(tx_tps, 1) if tx_tps else None,
        **device_keys,
    }
    out.update(_spread_warns(out))
    artifact.update(out)
    print(json.dumps(out))


def governor_section():
    """Closed-loop governor bench (docs/serving_robustness.md): drive
    a toy GenerateAPI through one seeded latency-ramp fault and
    measure the CONTROL LOOP, not throughput —

    - ``governor_demote_latency_ms``: fault-inject (first ramp stall)
      -> demote actuation;
    - ``governor_demote_to_recover_ms``: fault-inject -> tier demotion
      -> fault-clear -> full-fidelity restore (decoder back at the
      base tier), the whole closed loop's wall time;
    - ``governor_transitions``: demote+promote count for the seeded
      profile (2 = converged; more = oscillation — lower-better via
      the ``_transitions`` regress rule);
    - ``governor_tier_attainment_bf16`` / ``_int8``: per-tier SLO
      attainment (fraction of completed requests meeting the ttft
      objective), from the ledger rows' tier/quant attribution.
    """
    import urllib.request

    from veles_tpu.observe.governor import (GovernorConfig,
                                            ServingGovernor)
    from veles_tpu.observe.reqledger import RequestLedger
    from veles_tpu.observe.slo import SLOEngine, row_latencies
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import GenerateAPI
    from veles_tpu.serving_chaos import (ServingChaosConfig,
                                         ServingChaosMonkey)

    threshold_s = 0.150
    rng = numpy.random.RandomState(0)
    heads, embed, vocab = 4, 32, 64
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.1)
    engine = SLOEngine({"ttft_p95_ms": threshold_s * 1000.0},
                       windows=(2.0, 8.0), bucket_seconds=0.25)
    governor = ServingGovernor(GovernorConfig(
        demote_burn=2.0, recover_burn=1.0, cooldown_s=3.0,
        interval_s=0.05, ladder=("int8",), prewarm=False,
        breaker_guard=False))
    monkey = ServingChaosMonkey(ServingChaosConfig(
        seed=1, latency_ramp_ms=300.0, latency_ramp_steps=8,
        latency_ramp_hold=1 << 30))
    ledger = RequestLedger()
    api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                      n_tokens=5, chunk=2, port=0,
                      rebuild_backoff=0.02, slo=engine,
                      governor=governor, chaos=monkey, ledger=ledger)
    api.start()
    url = "http://127.0.0.1:%d/generate" % api.port
    prompt = [1, 2, 3]

    def post_one():
        req = urllib.request.Request(
            url, data=json.dumps({"tokens": prompt}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                resp.read()
        except Exception:
            pass

    def wait(predicate, timeout, tick=0.05, trickle=False):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if trickle:
                post_one()
            if predicate():
                return True
            time.sleep(tick)
        return False

    out = {}
    try:
        # fault-inject: the held ramp burns the ttft objective until
        # the governor demotes and the graceful swap lands
        demoted = wait(lambda: governor.demoted, 60, trickle=True)
        swapped = demoted and wait(
            lambda: api.decoder.quantize == "int8", 60, trickle=True)
        # fault-clear: a trickle of now-fast traffic shows the burn
        # decaying; the governor promotes and restores full fidelity
        monkey.clear_ramp()
        recovered = swapped and wait(
            lambda: not governor.demoted
            and (api.decoder.quantize or "bf16") == "bf16", 90,
            tick=0.1, trickle=True)
        recovered_at = time.monotonic()
        start = monkey.stamps.get("ramp_start")
        moves = [t for t in governor.transitions
                 if t["action"] in ("demote", "promote")]
        if recovered and start is not None and moves:
            out["governor_demote_latency_ms"] = round(
                (moves[0]["mono"] - start) * 1000.0, 1)
            out["governor_demote_to_recover_ms"] = round(
                (recovered_at - start) * 1000.0, 1)
            out["governor_transitions"] = len(moves)
        by_tier = {}
        for row in ledger.slowest(512):
            if row.get("outcome") != "completed":
                continue
            tier = row.get("tier") or row.get("quant") or "bf16"
            ttft, _ = row_latencies(row)
            if ttft is None:
                continue
            good, total = by_tier.setdefault(tier, [0, 0])
            by_tier[tier] = [good + (ttft <= threshold_s), total + 1]
        for tier, (good, total) in sorted(by_tier.items()):
            if total:
                out["governor_tier_attainment_"
                    + tier.replace("-", "")] = round(good / total, 4)
        out["governor_config"] = ("demote_burn=2,recover_burn=1,"
                                  "cooldown_s=3,ladder=int8,"
                                  "ramp=300ms×8+hold")
    finally:
        monkey.clear_ramp()
        api.stop()
    return out


def deploy_section(swaps=3):
    """Zero-downtime deploy bench (docs/zero_downtime.md): hot-swap
    live weights under sustained client traffic and measure the SEAM,
    not throughput —

    - ``deploy_swap_ms``: request_swap -> drain -> weight install ->
      probe decode -> resume, best wall time over ``swaps`` swaps
      (lower-better via the ``_ms`` regress rule);
    - ``deploy_swap_shed_requests``: non-200 responses observed by a
      client hammering /generate across every swap window — the
      zero-downtime contract pins this at 0 (the ``_shed_requests``
      regress rule watches the direction; a 0 baseline passes the
      ratio gate vacuously, so tests/test_deploy.py enforces the pin
      as a hard assert too).
    """
    import threading
    import urllib.error
    import urllib.request

    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import GenerateAPI

    rng = numpy.random.RandomState(0)
    heads, embed, vocab = 4, 32, 64
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.1)
    versions = [init_transformer_params(
        numpy.random.RandomState(7 + i), 2, embed, heads, vocab)
        for i in range(swaps)]
    api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                      n_tokens=5, chunk=2, port=0)
    api.start()
    url = "http://127.0.0.1:%d/generate" % api.port
    shed = []
    served = [0]
    stop = threading.Event()

    def pound():
        while not stop.is_set():
            req = urllib.request.Request(
                url, data=json.dumps({"tokens": [1, 2, 3]}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
                served[0] += 1
            except urllib.error.HTTPError as exc:
                shed.append(exc.code)
            except Exception:
                if not stop.is_set():
                    shed.append(-1)

    out = {}
    client = threading.Thread(target=pound)
    try:
        client.start()
        deadline = time.monotonic() + 30
        while not served[0] and time.monotonic() < deadline:
            time.sleep(0.01)  # warm the decode programs first
        best_ms = None
        for i, new_params in enumerate(versions):
            t0 = time.perf_counter()
            api.swap_params(new_params, version="bench-v%d" % (i + 2))
            swap_ms = (time.perf_counter() - t0) * 1000.0
            if best_ms is None or swap_ms < best_ms:
                best_ms = swap_ms
            time.sleep(0.1)  # traffic between swap windows
        out = {
            "deploy_swap_ms": round(best_ms, 1),
            "deploy_swap_shed_requests": len(shed),
            "deploy_swap_served_requests": served[0],
            "deploy_swaps": api.health.counter("param_swaps"),
            "deploy_config": "swaps=%d,slots=2,embed=%d" % (swaps,
                                                            embed),
        }
    finally:
        stop.set()
        client.join(60)
        api.stop()
    return out


def replay_section(requests=16):
    """Traffic record-replay round-trip fidelity
    (docs/traffic_replay.md): record a short staggered two-tenant
    trace from a live GenerateAPI's request ledger, replay it at 1x
    open-loop against a FRESH endpoint, and book the fidelity as
    regress-guarded numbers —

    - ``replay_fidelity_delivered_ratio``: tokens the replay delivered
      over tokens the recording delivered (higher-better default; a
      recorder or replayer that starts losing work fails the gate);
    - ``replay_schedule_skew_ms``: planned-vs-actual arrival skew p95
      of the open-loop replayer (lower-better via ``_ms`` — a replayer
      that cannot hold its schedule invalidates every capacity number
      built on it, observe/capacity.py).
    """
    import tempfile
    import urllib.request

    from veles_tpu.observe.replay import (load_trace, record_trace,
                                          replay, warp_plan)
    from veles_tpu.observe.reqledger import RequestLedger
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import GenerateAPI

    rng = numpy.random.RandomState(0)
    heads, embed, vocab = 4, 32, 64
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.1)

    def fresh_api():
        return GenerateAPI(params, table, heads, slots=2, max_len=32,
                           n_tokens=5, chunk=2, port=0,
                           ledger=RequestLedger())

    def post(url, tenant, n):
        req = urllib.request.Request(
            url, data=json.dumps({"tokens": [1 + i % 7
                                             for i in range(n)]}
                                 ).encode(),
            headers={"Content-Type": "application/json",
                     "X-Veles-Tenant": tenant})
        with urllib.request.urlopen(req, timeout=60) as resp:
            resp.read()

    api = fresh_api()
    api.start()
    trace_path = os.path.join(tempfile.mkdtemp(prefix="veles-replay-"),
                              "bench.trace.jsonl")
    try:
        url = "http://127.0.0.1:%d/generate" % api.port
        # the staggered-drain shape: interleaved tenants, ragged
        # prompt lengths, a deliberate arrival cadence to re-hit
        for i in range(requests):
            post(url, "acme" if i % 2 else "globex", 3 + i % 5)
            time.sleep(0.01 + 0.02 * (i % 3))
        record_trace(api.ledger, trace_path, source="bench")
    finally:
        api.stop()
    _, rows = load_trace(trace_path)
    recorded = sum(r["tokens"] for r in rows)
    api = fresh_api()
    api.start()
    try:
        plan = warp_plan(rows, warp=1.0, seed=0)
        summary = replay(plan,
                         url="http://127.0.0.1:%d" % api.port,
                         vocab=vocab, workers=4)
    finally:
        api.stop()
    return {
        "replay_fidelity_delivered_ratio":
            round(summary["delivered_ratio"], 4),
        "replay_schedule_skew_ms": summary["schedule_skew_ms_p95"],
        "replay_config": "requests=%d,recorded_tokens=%d,slots=2"
                         % (len(rows), recorded),
    }


#: same-seed CPU subprocess replica for the elastic bench — identical
#: weights to its twin so the router's failover stays bit-identical
#: (the same child tests/test_router.py's chaos acceptance boots).
#: Each decode dispatch is PACED by a deterministic slow-step chaos
#: profile: the toy model's compute is too small to bind a core, so
#: without pacing the 1-vs-2-replica ratio measures scheduler noise
#: on however many cores the bench host has (= 0.6-1.7x run to run
#: on one core). Paced, the replica is service-time-bound — sleeps
#: overlap across processes on any core count — and the ratio
#: isolates the quantity this section regress-gates: the FRONT's
#: ability to spread load across the ring.
_ELASTIC_CHILD = r"""
import json, time
import numpy
import jax.numpy as jnp
from veles_tpu.parallel.transformer_step import init_transformer_params
from veles_tpu.serving import GenerateAPI
from veles_tpu.serving_chaos import (ServingChaosConfig,
                                     ServingChaosMonkey)

rng = numpy.random.RandomState(0)
params = init_transformer_params(rng, 2, 16, 4, 11)
table = jnp.asarray(rng.randn(11, 16).astype(numpy.float32) * 0.3)
pacer = ServingChaosMonkey(ServingChaosConfig(seed=1, slow_step=1.0,
                                              slow_step_ms=8.0))
api = GenerateAPI(params, table, 4, slots=2, max_len=32, n_tokens=5,
                  chunk=2, port=0, chaos=pacer)
api.start()
print(json.dumps({"port": api.port}), flush=True)
while True:
    time.sleep(3600)
"""


def elastic_section(window_s=3.0, threads=8):
    """Elastic replicated serving bench (docs/elastic_serving.md):
    scale efficiency + the failover seam of the router front, over
    same-seed service-paced CPU subprocess replica twins (see
    ``_ELASTIC_CHILD`` for why they are paced) —

    - ``elastic_tokens_per_sec_{1replica,2replica}``: router-front
      decode throughput with 1 vs 2 replicas under the same client
      pressure, and ``elastic_scale_x`` = their ratio (the elastic
      claim: adding a replica buys near-linear goodput, >= 1.7x at
      toy sizes; a dropped ratio = the router became the bottleneck,
      higher-better under the regress sentinel);
    - ``elastic_failover_ms``: kill -9 one of the two replicas under
      live traffic and take the router's best measured fail-to-win
      latency (attempt failure -> winning offer on the next replica;
      lower-better via the ``_ms`` regress rule);
    - ``elastic_affinity_hit_rate``: the fraction of keyed requests
      the ring routed to their primary prefix-cache owner during the
      2-replica window (affinity decayed = prefix caches go cold
      across the spread).
    """
    import signal
    import subprocess
    import sys
    import threading
    import urllib.request

    from veles_tpu.router import build_router

    spec = ("poll_interval_s=0.2,fail_threshold=2,cooldown_s=0.0,"
            "hedge_after_s=5.0,backoff_s=0.01,page_size=4")
    repo = os.path.dirname(os.path.abspath(__file__))

    def spawn(n):
        env = _cpu8_env()
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        procs, urls = [], []
        try:
            for _ in range(n):
                procs.append(subprocess.Popen(
                    [sys.executable, "-c", _ELASTIC_CHILD], env=env,
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, cwd=repo))
            for proc in procs:
                line = proc.stdout.readline()
                if not line:
                    raise RuntimeError("replica died: %s"
                                       % proc.stderr.read()[-2000:])
                urls.append("http://127.0.0.1:%d"
                            % json.loads(line)["port"])
        except Exception:
            for proc in procs:
                proc.kill()
            raise
        return procs, urls

    def post(url, tokens, timeout=60):
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"tokens": tokens}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read().decode())

    # page-aligned (page_size=4) distinct-prefix prompts: each rides
    # affinity to one owner, spreading the set across the ring
    prompts = [[i + 1, i + 2, i + 3, i + 4] for i in range(threads)]

    def pound_window(front, seconds):
        done = [0]
        stop = threading.Event()
        lock = threading.Lock()

        def pound(prompt):
            while not stop.is_set():
                try:
                    body = post(front, prompt)
                except Exception:
                    continue
                with lock:
                    done[0] += len(body.get("tokens", ()))

        workers = [threading.Thread(target=pound, args=(p,))
                   for p in prompts]
        for t in workers:
            t.start()
        t0 = time.perf_counter()
        time.sleep(seconds)
        elapsed = time.perf_counter() - t0
        stop.set()
        for t in workers:
            t.join(60)
        return done[0] / elapsed

    def measure(n):
        procs, urls = spawn(n)
        plane, router = build_router(urls, spec=spec)
        router.start()
        try:
            front = "http://127.0.0.1:%d" % router.port
            for url in urls:  # warm each replica's decode program
                post(url, [1, 2, 3, 4])
            post(front, prompts[0])
            rate = pound_window(front, window_s)
            snap = router.snapshot()
            failover_ms = None
            if n > 1:
                # the failover seam: kill -9 replica 0 under load,
                # take the router's best fail-to-win sample
                stop = threading.Event()

                def pound(prompt):
                    while not stop.is_set():
                        try:
                            post(front, prompt)
                        except Exception:
                            continue

                workers = [threading.Thread(target=pound, args=(p,))
                           for p in prompts]
                for t in workers:
                    t.start()
                time.sleep(0.3)
                procs[0].send_signal(signal.SIGKILL)
                deadline = time.monotonic() + 20
                while not router.failover_ms_samples() \
                        and time.monotonic() < deadline:
                    time.sleep(0.05)
                stop.set()
                for t in workers:
                    t.join(60)
                samples = router.failover_ms_samples()
                failover_ms = min(samples) if samples else None
            return rate, snap, failover_ms
        finally:
            router.stop()
            for proc in procs:
                proc.kill()

    rate1, _, _ = measure(1)
    rate2, snap2, failover_ms = measure(2)
    hits = snap2["counters"].get("affinity_hits", 0)
    misses = snap2["counters"].get("affinity_misses", 0)
    out = {
        "elastic_tokens_per_sec_1replica": round(rate1, 1),
        "elastic_tokens_per_sec_2replica": round(rate2, 1),
        "elastic_scale_x": round(rate2 / rate1, 3) if rate1 else None,
        "elastic_affinity_hit_rate": round(
            hits / (hits + misses), 3) if hits + misses else None,
        "elastic_config": "replicas=1v2,slots=2,threads=%d,"
                          "window=%.1fs,paced_8ms,cpu_subprocess"
                          % (threads, window_s),
    }
    if failover_ms is not None:
        out["elastic_failover_ms"] = round(failover_ms, 1)
    return out


def history_section():
    """Metric flight recorder bench (docs/observability.md): the cost
    of always-on trend memory, and how fast it notices a fault —

    - ``history_sample_off_ns`` / ``history_sample_on_ns``:
      steady-state nanoseconds per registry sample without/with the
      history store (rings + seed rules) attached — the embedded
      recorder's whole tax, lower-better via the ``_ns`` regress rule;
    - ``incident_mttd_ms``: seeded latency-ramp fault injection ->
      first anomaly firing (the detector's mean time to detect);
    - ``history_anomaly_rate``: rule firings per sample over the chaos
      window (a noisier detector regressed — the ``_anomaly_rate``
      rule);
    - ``incident_leading_series``: which series the incident artifact
      named as the leading indicator (string, not compared).
    """
    import tempfile
    import urllib.request

    from veles_tpu.observe.history import (AnomalyRule,
                                           IncidentRecorder,
                                           MetricHistory,
                                           default_rules,
                                           get_metric_history,
                                           set_metric_history)
    from veles_tpu.observe.metrics import (MetricsRegistry,
                                           get_metrics_registry)
    from veles_tpu.observe.reqledger import RequestLedger
    from veles_tpu.observe.slo import SLOEngine
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import GenerateAPI
    from veles_tpu.serving_chaos import (ServingChaosConfig,
                                         ServingChaosMonkey)

    out = {}
    # -- sampler overhead: a synthetic registry with a representative
    # series population, sampled bare vs through the history store
    bench_reg = MetricsRegistry(enabled=True)
    for i in range(64):
        bench_reg.set("veles_bench_gauge", float(i),
                      labels={"lane": str(i)})
        bench_reg.counter_set("veles_bench_total", 100 + i,
                              labels={"lane": str(i)})
        bench_reg.observe("veles_bench_seconds", 0.001 * i,
                          labels={"lane": str(i % 8)})
    reps = 200
    start = time.perf_counter()
    for _ in range(reps):
        bench_reg.sample()
    out["history_sample_off_ns"] = round(
        (time.perf_counter() - start) / reps * 1e9, 1)
    bench_hist = MetricHistory(
        registry=bench_reg, interval_s=0.0, capacity=256,
        rules=default_rules(),
        incidents=IncidentRecorder(cooldown_s=3600.0,
                                   directory=tempfile.mkdtemp()))
    for _ in range(8):  # warm the rings to steady state
        bench_hist.sample()
    start = time.perf_counter()
    for _ in range(reps):
        bench_hist.sample()
    out["history_sample_on_ns"] = round(
        (time.perf_counter() - start) / reps * 1e9, 1)

    # -- chaos-driven MTTD: a seeded latency ramp burns the ttft
    # objective; measure fault-inject -> first anomaly firing
    threshold_s = 0.150
    rng = numpy.random.RandomState(0)
    heads, embed, vocab = 4, 32, 64
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.1)
    engine = SLOEngine({"ttft_p95_ms": threshold_s * 1000.0},
                       windows=(2.0, 8.0), bucket_seconds=0.25)
    # incident cooldown 0 so the LAST artifact (the slo_burn-triggered
    # one) carries both breaching rules; each rule fires once. The
    # latency rule exists so the leading indicator is a measurement —
    # the gauge updates at first token, before the burn can resolve
    hist = MetricHistory(
        registry=get_metrics_registry(), interval_s=0.1,
        incidents=IncidentRecorder(cooldown_s=0.0,
                                   directory=tempfile.mkdtemp()))
    hist.add_rule(AnomalyRule(
        "ttft_p95_high", "veles_serving_latency_ms",
        match={"kind": "ttft", "quantile": "p95"}, kind="threshold",
        op=">=", threshold=threshold_s * 500.0, for_samples=1,
        cooldown_s=3600.0))
    hist.add_rule(AnomalyRule(
        "slo_burn", "veles_slo_burn_rate", kind="threshold", op=">=",
        threshold=2.0, for_samples=1, cooldown_s=3600.0))
    previous = get_metric_history()
    set_metric_history(hist)
    monkey = ServingChaosMonkey(ServingChaosConfig(
        seed=1, latency_ramp_ms=300.0, latency_ramp_steps=8,
        latency_ramp_hold=1 << 30))
    api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                      n_tokens=5, chunk=2, port=0,
                      rebuild_backoff=0.02, slo=engine, chaos=monkey,
                      ledger=RequestLedger())
    api.start()
    url = "http://127.0.0.1:%d/generate" % api.port
    samples_before = hist.samples_total
    burn_rule = hist.rules[-1]
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline \
                and not burn_rule.fired_total:
            req = urllib.request.Request(
                url, data=json.dumps({"tokens": [1, 2, 3]}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
            except Exception:
                pass
            hist.maybe_sample()
        doc = hist.incidents.last_doc
        ramp_start = monkey.stamps.get("ramp_start")
        first_fire = min((r.last_fired for r in hist.rules
                          if r.last_fired is not None),
                         default=None)
        if doc is not None and ramp_start is not None \
                and first_fire is not None:
            out["incident_mttd_ms"] = round(
                (first_fire - ramp_start) * 1000.0, 1)
            out["incident_leading_series"] = \
                doc["leading_indicator"]["series"]
        window_samples = hist.samples_total - samples_before
        if window_samples:
            out["history_anomaly_rate"] = round(
                hist.anomalies_total / window_samples, 4)
        out["history_config"] = ("interval_s=0.1,rules=ttft_p95_high"
                                 "+slo_burn,ramp=300msx8+hold")
    finally:
        monkey.clear_ramp()
        api.stop()
        set_metric_history(previous)
    return out


def memscope_section():
    """Per-owner HBM attribution bench (docs/memscope.md) —

    - ``hbm_owner_params_bytes`` / ``hbm_owner_kv_pool_bytes``: what
      the toy serving engine's owners report at fixed geometry — an
      owner's footprint quietly growing is a regression (the
      ``_bytes`` rule);
    - ``hbm_untagged_fraction``: DELTA-based attribution coverage —
      of the device bytes the toy engine's construction added, the
      share the registered accountants could not explain (process-wide
      residue would be all the earlier bench sections' arrays, not a
      coverage signal). Regresses UP via ``_untagged_fraction``;
    - ``headroom_forecast_s``: the forecast math on a fixed synthetic
      pool ramp (2 pages/s net growth against 10 free) — drifting
      means the slope fit changed, higher-better;
    - ``memscope_leak_named_owner``: the chaos leak-injection run's
      verdict owner (string, not compared) — the retained-pool zombie
      the breaker-rebuild edge diff must name, with its incident
      artifact path booked beside it.
    """
    import urllib.request

    from veles_tpu.observe.memscope import MemScope, set_memscope
    from veles_tpu.observe.reqledger import RequestLedger
    from veles_tpu.parallel.transformer_step import (
        init_transformer_params)
    from veles_tpu.serving import GenerateAPI
    from veles_tpu.serving_chaos import (ServingChaosConfig,
                                         ServingChaosMonkey)

    out = {}
    rng = numpy.random.RandomState(0)
    heads, embed, vocab = 4, 32, 64
    params = init_transformer_params(rng, 2, embed, heads, vocab)
    table = jnp.asarray(rng.randn(vocab, embed).astype(numpy.float32)
                        * 0.1)
    # a fresh scope: the toy engine's owners only — earlier bench
    # sections' decoders/bundles must not pollute the coverage number
    scope = MemScope(leak_min_bytes=1024)
    previous = set_memscope(scope)
    used_before, _ = scope.device_totals()
    monkey = ServingChaosMonkey(ServingChaosConfig(
        seed=1, leak_retain_pool_at=2))
    api = GenerateAPI(params, table, heads, slots=2, max_len=32,
                      n_tokens=5, chunk=2, port=0, paged=True,
                      page_size=8, rebuild_backoff=0.02, chaos=monkey,
                      ledger=RequestLedger())
    api.start()
    url = "http://127.0.0.1:%d/generate" % api.port
    try:
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline and not scope.leaks_total:
            req = urllib.request.Request(
                url, data=json.dumps({"tokens": [1, 2, 3]}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
            except Exception:
                pass
        owners = scope.attribute()
        for owner in ("params", "kv_pool"):
            if owners.get(owner):
                out["hbm_owner_%s_bytes" % owner] = owners[owner]
        used_after, _ = scope.device_totals()
        delta = used_after - used_before
        if delta > 0:
            tagged = sum(owners.values())
            out["hbm_untagged_fraction"] = round(
                max(0, delta - tagged) / delta, 4)
        verdict = next((edge for edge in reversed(scope.edges)
                        if edge["leak"]), None)
        if verdict is not None:
            out["memscope_leak_named_owner"] = verdict["owner"]
        # the rebuild seam flushes the incident artifact just AFTER
        # the verdict lands — give the driver a beat to finish it
        settle = time.monotonic() + 10.0
        while time.monotonic() < settle and not any(
                v.get("artifact") for v in scope.incidents):
            time.sleep(0.05)
        incident = next((v for v in reversed(scope.incidents)
                         if v.get("artifact")), None)
        if incident is not None:
            out["memscope_leak_artifact"] = incident["artifact"]
        out["memscope_config"] = ("paged=1,slots=2,"
                                  "leak_retain_pool_at=2")
    finally:
        monkey.release_leak()
        api.stop()
        set_memscope(previous)
    # the forecast math on a FIXED synthetic ramp (the live toy run's
    # slope depends on scheduling): 6 points over 5 s, used pages
    # growing 2/s net, 10 free at the newest point -> 5 s to empty
    probe = MemScope()
    base = time.monotonic()
    for i in range(6):
        probe._pool_points.append((base - (5 - i) * 1.0, 2 * i,
                                   20 - 2 * i))
    forecast = probe.headroom_forecast_s(now=base)
    if forecast is not None:
        out["headroom_forecast_s"] = round(forecast, 3)
    return out


def serve_main(profile_dir=None, artifact_path=None):
    """``make bench-serve``: the continuous-batching serving bench
    standalone (one JSON line) — fast iteration on the slot-engine hot
    path without paying for the full training bench. Runs the bf16
    tier and, when the device has the int8 kernels' appetite, the
    int8-KV slot tier too.

    The metrics registry is enabled for the window, so the decoder's
    per-dispatch histograms (veles_decode_*_seconds) accumulate across
    both tiers and their bucketed summaries land in the JSON — the
    perf trajectory carries host-overhead DISTRIBUTIONS between
    rounds, not just totals. ``--profile-dir DIR`` additionally wraps
    the window in a jax profiler capture with span-named device
    annotations (docs/observability.md)."""
    from veles_tpu.observe.metrics import get_metrics_registry
    from veles_tpu.observe.profile import profile_window

    registry = get_metrics_registry()
    was_enabled = registry.enabled
    registry.enable()
    artifact = _make_artifact(artifact_path
                              or "BENCH_serve_artifact.json")
    kind = device_info()[0]
    out = {"metric": "decode_continuous_tokens_per_sec",
           "unit": "tokens/sec", "device_kind": kind}
    artifact.update(out)
    try:
        with profile_window(profile_dir):
            section = _guarded(decode_continuous, fallback={})
            out.update(section)
            artifact.update(section)
            section = _guarded(decode_continuous, quantize="int8-kv",
                               fallback={})
            out.update(section)
            artifact.update(section)
            # the paged-KV section (docs/paged_kv.md): length flatness,
            # cold-vs-cached admission, concurrency at fixed HBM
            section = _guarded(decode_paged, fallback={})
            out.update(section)
            artifact.update(section)
            # the fused paged-attention kernel (docs/paged_kv.md "The
            # fused kernel"): per-length step flatness + the
            # mixed-length kernel-vs-gather speedup at ragged
            # occupancy (interpret-mode emulation off TPU)
            section = _guarded(decode_paged_kernel, fallback={})
            out.update(section)
            artifact.update(section)
            # the mesh tier (docs/sharded_serving.md): train<->serve
            # reshard bytes/latency + the sharded slot engine's step
            # time ride the serving bench too, so `make bench-serve`
            # alone guards the whole serving surface incl. the pod path
            section = _guarded(reshard_bench, fallback={})
            out.update(section)
            artifact.update(section)
            # AOT cold start (docs/aot_artifacts.md): live trace+compile
            # vs bundle deserialize+execute, fresh-subprocess twins —
            # coldstart_compiles pinned 0 is the zero-retrace proof
            section = _guarded(coldstart_section, fallback={})
            out.update(section)
            artifact.update(section)
            # the closed-loop governor (docs/serving_robustness.md):
            # fault->demote->recover wall time, transition count and
            # per-tier SLO attainment under a seeded latency ramp
            section = _guarded(governor_section, fallback={})
            out.update(section)
            artifact.update(section)
            # zero-downtime deploys (docs/zero_downtime.md): hot-swap
            # wall time under live traffic, with the shed-request
            # count pinned 0 (the zero-downtime contract)
            section = _guarded(deploy_section, fallback={})
            out.update(section)
            artifact.update(section)
            # traffic record-replay round trip
            # (docs/traffic_replay.md): trace a staggered two-tenant
            # run off the request ledger, replay it 1x open-loop
            # against a fresh endpoint — delivered-token ratio and
            # schedule-skew p95 are the regress-guarded fidelity
            section = _guarded(replay_section, fallback={})
            out.update(section)
            artifact.update(section)
            # elastic replicated serving (docs/elastic_serving.md):
            # router-front scale efficiency 1 -> 2 subprocess
            # replicas, the kill -9 fail-to-win latency, and the
            # prefix-affinity hit rate across the spread
            section = _guarded(elastic_section, fallback={})
            out.update(section)
            artifact.update(section)
            # the metric flight recorder (docs/observability.md):
            # sampler overhead with history on vs off, and the
            # chaos-driven incident MTTD + anomaly rate
            section = _guarded(history_section, fallback={})
            out.update(section)
            artifact.update(section)
            # the serving goodput observatory (docs/observability.md
            # "Serving goodput + slot timeline"): useful-vs-waste
            # token decomposition + slot occupancy of a staggered
            # drain, with the per-cause shares regress-gated
            section = _guarded(servescope_section, fallback={})
            out.update(section)
            artifact.update(section)
            # the HBM attribution plane (docs/memscope.md): per-owner
            # bytes + attribution coverage of a toy paged engine, the
            # headroom-forecast math on a fixed ramp, and the chaos
            # retained-pool leak verdict's named owner
            section = _guarded(memscope_section, fallback={})
            out.update(section)
            artifact.update(section)
        out["decode_histograms"] = registry.histogram_summary(
            "veles_decode")
    finally:
        if not was_enabled:
            registry.disable()
    out["value"] = out.get("decode_continuous_tokens_per_sec")
    out.update(_spread_warns(out))
    artifact.update(out)
    print(json.dumps(out))


def _flag_value(argv, flag):
    for i, arg in enumerate(argv):
        if arg == flag and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith(flag + "="):
            return arg.split("=", 1)[1]
    return None


if __name__ == "__main__":
    import sys

    if "--serve" in sys.argv[1:]:
        serve_main(profile_dir=_flag_value(sys.argv[1:],
                                           "--profile-dir"),
                   artifact_path=_flag_value(sys.argv[1:],
                                             "--artifact"))
    else:
        main(artifact_path=_flag_value(sys.argv[1:], "--artifact"))
