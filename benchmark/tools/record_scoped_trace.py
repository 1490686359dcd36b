#!/usr/bin/env python3
"""Record the small capture and scope table that
``benchmark/tests/test_scopes.py`` reads.

    python benchmark/tools/record_scoped_trace.py [--out chiprun_out]

On a TPU: a toy two-layer ``build_tick`` train sweep (4 steps of 8
rows) and a toy two-block decoder (2 slots, chunks of 2 steps) run
three times each inside one traced window with the program's spans on,
as the cells' programs do at their size. Writes
``scoped_trace.xplane.pb`` (the capture) and ``scoped_table.json``
(``{function fragment: scope_table(fragment)}``, the instructions as
``[name, shape, op_name]`` rows); both go to ``benchmark/tests/data``
by hand. Not used by the driver.
"""

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--out", default=os.path.join(ROOT,
                                                      "chiprun_out"))
    args = parser.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy

    from benchmark.harness import common
    from veles_tpu.observe import xla_stats
    from veles_tpu.parallel import fused
    from veles_tpu.parallel.transformer_step import init_transformer_params
    from veles_tpu.serving import ContinuousDecoder

    common.device_facts(1)
    rng = numpy.random.RandomState(0)
    specs = [dict(kind="dense", activation=act, leaves=fused._WB_LEAVES,
                  has_params=True, solver="momentum")
             for act in ("tanh", "linear")]
    sweep = fused.build_tick(specs, "none")[2]

    def layer(a, b):
        return {"p": {"w": jnp.asarray(rng.randn(a, b), jnp.float32) * .1,
                      "b": jnp.zeros(b)},
                "v": {"w": jnp.zeros((a, b)), "b": jnp.zeros(b)}}

    params = [layer(128, 256), layer(256, 8)]
    rest = ([jnp.asarray([0.01, 0.01, 0.0, 0.0, 0.9], jnp.float32)] * 2,
            {}, jnp.asarray(rng.randn(32, 128), jnp.float32),
            jnp.asarray(rng.randint(0, 8, 32)),
            numpy.arange(32).reshape(4, 8), numpy.full(4, 8, numpy.int32),
            numpy.float32(32), numpy.zeros(4, numpy.int64))
    decoder = ContinuousDecoder(
        init_transformer_params(rng, 2, 128, 2, 64),
        jnp.asarray(rng.randn(64, 128).astype(numpy.float32) * 0.3),
        2, slots=2, max_len=256, n_tokens=6)

    def work(params):
        for _ in range(3):
            params, (loss, _) = sweep(params, *rest)
        jax.block_until_ready(loss)
        decoder.submit([1, 2, 3])
        decoder.submit([4, 5, 6, 7, 8])
        decoder.drain_pipelined(2)
        return params

    params = work(params)                       # warm: compiles
    window = common.TracedWindow("scoped")
    window.start()
    with jax.profiler.TraceAnnotation("benchmark.window"):
        work(params)
    path = window.stop()
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(path, os.path.join(args.out, "scoped_trace.xplane.pb"))
    tables = {}
    for fragment in ("train_sweep", "slot_step_many"):
        tables[fragment] = [
            dict(table, instructions=[
                [name, shape, op_name] for name, (shape, op_name)
                in table["instructions"].items()])
            for table in xla_stats.scope_table(fragment)]
    with open(os.path.join(args.out, "scoped_table.json"), "w") as fout:
        json.dump(tables, fout, separators=(",", ":"))
    window.discard()
    shutil.rmtree(common.run_dir(), ignore_errors=True)
    print(json.dumps({name: [len(t["instructions"]) for t in found]
                      for name, found in tables.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
