"""Time one routed-expert layer through both tilings, by rows.

What ``ops/moe.STREAM_MAX_ROWS`` is read from (PERF.md §5). Prints ONE
JSON line. Only the TPU gives times worth a name:

    chiprun -- python -m veles_tpu.scripts.moe_rows_sweep
"""

import json
import math
import time

import numpy

import jax
import jax.numpy as jnp
from jax import lax

from veles_tpu.observe import xla_stats
from veles_tpu.ops import moe


def device_info():
    """(device_kind, peak_bf16_tflops) of the device: the peak is the
    exact-match row of ``xla_stats.PEAK_BF16_TFLOPS`` (an unlisted TPU
    kind raises there), None off the TPU."""
    return jax.devices()[0].device_kind, xla_stats.peak_tflops()


def moe_rows_sweep(rows=(64, 128, 256, 512, 1024, 2048, 4096, 8192),
                   count=256, width=2048, inner=768, top_k=8, steps=8,
                   repeats=5):
    """The routed experts' products of ONE expert layer through both
    tilings (``ops/moe.streamed_experts``: each touched expert once,
    its matrices whole; ``ops/moe.grouped_experts``:
    ``jax.lax.ragged_dot``) at each number of rows (assignments:
    tokens x ``top_k``, every token ``top_k`` distinct experts chosen
    uniformly), at the benchmark's expert model's published widths by
    default.

    Per row count: the touched experts, ``streamed_ms`` and
    ``grouped_ms`` a call (median of ``repeats`` timings of ``steps``
    calls chained inside one program, each call's rows moved by the
    one before so that none is hoisted), the gigabytes a second the
    touched experts' matrices alone make of the streamed time, and the
    widest gap between the two results (the gate is float32 in the
    one, the rows' type in the other)."""
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


if __name__ == "__main__":
    print(json.dumps(moe_rows_sweep()))
