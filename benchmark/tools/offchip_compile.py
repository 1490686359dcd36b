"""Compile the timed programs at real size for a described v5e, with no
chip attached (``JAX_PLATFORMS=cpu python benchmark/tools/offchip_compile.py
<config> [slots]``): what the chip's compiler would refuse costs no chip
time. Prints each program's compile seconds and its memory analysis.
Nothing runs, so this says nothing about results or times on the chip.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def serve_programs(config, slots, which):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from veles_tpu.parallel import decode

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    e, v = config["n_embd"], config["vocab_size"]
    heads, layers = config["n_head"], config["n_layer"]
    hidden, max_len = config["n_inner"], config["serving"]["max_len"]
    bf = jnp.bfloat16
    block = {"ln1_w": spec((e,), bf), "ln1_b": spec((e,), bf),
             "wqkv": spec((e, 3 * e), bf), "bqkv": spec((3 * e,), bf),
             "wout": spec((e, e), bf), "bout": spec((e,), bf),
             "ln2_w": spec((e,), bf), "ln2_b": spec((e,), bf),
             "w1": spec((e, hidden), bf), "b1": spec((hidden,), bf),
             "w2": spec((hidden, e), bf), "b2": spec((e,), bf)}
    params = {"blocks": [block] * layers, "lnf_w": spec((e,), bf),
              "lnf_b": spec((e,), bf), "head": spec((e, v), bf)}
    table = spec((v, e), bf)
    kv = (layers, slots, max_len, heads, e // heads)
    state = {"lengths": spec((slots,), jnp.int32),
             "logits": spec((slots, v), jnp.float32),
             "req_key": jax.eval_shape(
                 lambda: jax.random.split(jax.random.key(0), slots)),
             "step": spec((slots,), jnp.int32),
             "k": spec(kv, bf), "v": spec(kv, bf)}
    state["req_key"] = jax.ShapeDtypeStruct(
        state["req_key"].shape, state["req_key"].dtype, sharding=chip)
    for name in which:
        kind, a, b = name.split(":")
        a, b = int(a), int(b)
        t0 = time.perf_counter()
        if kind == "step":      # step:<chunk>:<span>
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
                spec((b, a, e), bf),
                jax.ShapeDtypeStruct(keys.shape, keys.dtype,
                                     sharding=chip),
                spec((b,), jnp.int32))
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
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
                text.count("remat_uncompressed = ")}), flush=True)


if __name__ == "__main__":
    with open(os.path.join(ROOT, sys.argv[1])) as fin:
        cfg = json.load(fin)
    serve_programs(cfg, int(sys.argv[2]), sys.argv[3:])
