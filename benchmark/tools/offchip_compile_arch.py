"""Compile a serving configuration's timed programs at real size for a
described v5e, with no chip attached, whatever block the configuration
declares (``JAX_PLATFORMS=cpu python
benchmark/tools/offchip_compile_arch.py <config> init admit:<bucket>:<group>
step:<chunk>:<span> ...``): what the chip's compiler would refuse costs
no chip time. The parameters' shapes come from the reference's own
``init_params`` and the state's from ``init_slot_state``, both through
``jax.eval_shape``, so the architecture rides as it does in a run.
Prints each program's compile seconds and its memory analysis; with
``--text DIR`` also writes each program's compiled text there. Nothing
runs, so this says nothing about results or times on the chip.
"""

import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def serve_programs(config, which, text_dir=None):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import common
    from veles_tpu.parallel import blocks, decode

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=chip), tree)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    reference = common.load_module(config["reference"])
    serving = config["serving"]
    heads, slots = config["n_head"], serving["slots"]
    params, table = jax.eval_shape(
        functools.partial(reference.init_params, 0, config))
    arch = blocks.arch_of(params)
    params, table = on_chip((params, table))
    e, v = table.shape[1], table.shape[0]
    state = on_chip(jax.eval_shape(functools.partial(
        decode.init_slot_state, len(params["blocks"]), slots,
        serving["max_len"], heads, e // heads, v, dtype=table.dtype,
        arch=arch)))
    for name in which:
        kind, _, sizes = name.partition(":")
        a, b = (int(n) for n in sizes.split(":")) if sizes else (0, 0)
        t0 = time.perf_counter()
        if kind == "init":      # the weights' own program
            lowered = jax.jit(
                functools.partial(reference.init_params, 0, config),
                out_shardings=chip).lower()
        elif kind == "step":    # step:<chunk>:<span>
            lowered = jax.jit(
                decode._slot_step_many,
                static_argnames=("heads", "n", "sample", "top_k",
                                 "span"),
                donate_argnames=("state",)).lower(
                params, table, heads, state, spec((slots,), jnp.bool_),
                a, spec((), jnp.float32), sample=False, top_k=0, span=b)
        else:                   # admit:<bucket>:<group>
            keys = jax.eval_shape(
                lambda: jax.random.split(jax.random.key(0), b))
            lowered = jax.jit(
                decode._slot_admit_many, static_argnames=("heads",),
                donate_argnames=("state",)).lower(
                params, table, heads, state, spec((b,), jnp.int32),
                spec((b, a, e), table.dtype),
                jax.ShapeDtypeStruct(keys.shape, keys.dtype,
                                     sharding=chip),
                spec((b,), jnp.int32))
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(
                    text_dir, name.replace(":", "_") + ".txt"), "w") as out:
                out.write(text)
        print(json.dumps({
            "program": name, "slots": slots,
            "compile_s": round(time.perf_counter() - t0, 1),
            "temp_bytes": mem.temp_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            # whole-slab copies the compiler put round the per-slot
            # cache appends (the decode chunk's cliff, PERF.md)
            "remat_uncompressed_copies":
                text.count("remat_uncompressed = "),
            "ragged_dots": text.count("ragged_dot_tiling")}), flush=True)


if __name__ == "__main__":
    argv = sys.argv[1:]
    text_dir = None
    if "--text" in argv:
        at = argv.index("--text")
        text_dir = argv[at + 1]
        del argv[at:at + 2]
    with open(os.path.join(ROOT, argv[0])) as fin:
        cfg = json.load(fin)
    serve_programs(cfg, argv[1:], text_dir)
