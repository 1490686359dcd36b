"""Time one routed-expert layer through its three tilings, by rows.

What ``ops/moe.STREAM_MAX_ROWS``, ``ops/moe.ROW_TILE`` and
``ops/moe.SLICED_ROW_TILE`` are read from (PERF.md §5). Prints ONE JSON
line a call. Only the TPU gives times worth a name; the widths are
those of the benchmark's first expert model unless told (its second: 32
experts of 2048 x 1792, top-4; the held share of Command A+'s: 16
experts of 4096 x 4096, whose kernels take the experts in slices of
their inner width, each slice width given here timed apart), on a
TPU host:

    python -m veles_tpu.scripts.moe_rows_sweep
    python -m veles_tpu.scripts.moe_rows_sweep \\
        --count 32 --inner 1792 --top-k 4
    python -m veles_tpu.scripts.moe_rows_sweep \\
        --count 16 --width 4096 --inner 4096 --top-k 8 \\
        --rows 384 2048 8192 65536 --tiles 256 512 \\
        --slice-widths 2048 1024 512 --resident-rows 512
"""

import argparse
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


ROWS = (64, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768)


def moe_rows_sweep(rows=ROWS, count=256, width=2048, inner=768, top_k=8,
                   tiles=(moe.ROW_TILE,), slice_widths=None,
                   resident_rows=8192, steps=8, repeats=5):
    """The routed experts' products of ONE expert layer through its
    tilings (``ops/moe.grouped_experts``: ``jax.lax.ragged_dot``;
    ``ops/moe.streamed_experts``: each touched expert once, its
    matrices whole, rows and result resident, so only to
    ``resident_rows``; ``ops/moe.tiled_experts``: the same with the
    rows and the result a tile at a time, at each of ``tiles`` rows a
    tile) at each number of rows (assignments: tokens x ``top_k``,
    every token ``top_k`` distinct experts chosen uniformly), at the
    benchmark's first expert model's published widths by default. Each
    kernel takes its experts in slices of each of ``slice_widths`` of
    their inner width (None: the widest that ``ops/moe.widest_slice``
    gives it); a kernel whose claim the chip's VMEM does not hold is
    not timed.

    Per row count: the touched experts, ``grouped_ms``, ``streamed_ms``
    and ``tiled_<tile>_ms`` a call (``_f<slice>`` before ``_ms`` where
    the kernel takes its experts in slices; median of ``repeats``
    timings of ``steps`` calls chained inside one program, each call's
    rows moved by the one before so that none is hoisted), the
    gigabytes a second the touched experts' matrices alone make of each
    kernel's time, the teraflops the rows' products make of it, and the
    widest gap of each kernel's result from ``ragged_dot``'s (the gate
    is float32 in the kernels, the rows' type in the other)."""
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

    def streamed(sliced):
        return lambda x, load, experts: moe.streamed_experts(
            x, moe.visit_table(load, x.shape[0]), experts, sliced)

    def tiled(tile, sliced):
        return lambda x, load, experts: moe.tiled_experts(
            x, moe.tile_table(load, x.shape[0], tile), experts, tile,
            sliced)

    def part(sliced):
        """A kernel's name's suffix: none for the expert whole."""
        return "" if sliced == inner else "_f%d" % sliced

    def widths(held, tile):
        """The slice widths a kernel holding ``held`` rows is timed at:
        those whose claim the chip's VMEM holds."""
        if slice_widths is None:
            widest = moe.widest_slice(held, experts, tile)
            return [] if widest is None else [widest]
        mib = moe.VMEM_MIB.get(moe.device_kind())
        return [f for f in slice_widths if inner % f == 0 and (
            mib is None or moe._vmem_claim(jnp.bfloat16, held, experts,
                                           tile, f) <= mib << 20)]

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
        want = moe.grouped_experts(x, load, experts)
        line["grouped_ms"] = round(median_ms(
            chained(moe.grouped_experts), (x, load, experts)), 4)
        kernels = {}
        if n <= resident_rows:
            for sliced in widths(n, moe._ROW_TILE):
                kernels["streamed" + part(sliced)] = streamed(sliced)
        for tile in tiles:
            if n % tile == 0:
                for sliced in widths(tile, tile):
                    kernels["tiled_%d%s" % (tile, part(sliced))] = tiled(
                        tile, sliced)
        for name, kernel in kernels.items():
            took = round(median_ms(chained(kernel), (x, load, experts)), 4)
            line[name + "_ms"] = took
            line[name + "_gb_per_s"] = round(
                touched * matrices / took / 1e6, 1)
            line[name + "_gap"] = float(jnp.abs(
                kernel(x, load, experts) - want).max())
            line[name + "_tflops"] = round(n * matrices / took / 1e9, 1)
        out["rows"].append(line)
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--count", type=int, default=256)
    parser.add_argument("--width", type=int, default=2048)
    parser.add_argument("--inner", type=int, default=768)
    parser.add_argument("--top-k", type=int, default=8)
    parser.add_argument("--rows", type=int, nargs="+", default=ROWS)
    parser.add_argument("--tiles", type=int, nargs="+",
                        default=[moe.ROW_TILE])
    parser.add_argument("--slice-widths", type=int, nargs="+")
    parser.add_argument("--resident-rows", type=int, default=8192)
    args = parser.parse_args(argv)
    print(json.dumps(moe_rows_sweep(
        rows=args.rows, count=args.count, width=args.width,
        inner=args.inner, top_k=args.top_k, tiles=args.tiles,
        slice_widths=args.slice_widths,
        resident_rows=args.resident_rows)))


if __name__ == "__main__":
    main()
