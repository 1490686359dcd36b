"""Every Pallas kernel a TPU path can select must lower for the TPU.

The fused paged-attention kernel shipped (PR 18) having only ever run
under ``interpret=True``; its dots put the batch dimension in the
middle and Mosaic refused them at lowering — on the default TPU serving
path. Two checks keep that from recurring, neither needing a chip:

- ``jax.export.export(jax.jit(f), platforms=["tpu"])`` runs the
  Pallas→Mosaic lowering cross-platform, on the CPU, in seconds (tier-1);
- the installed libtpu can also COMPILE for a v5e it does not have
  (``jax.experimental.topologies``, a compile-only client), which runs
  Mosaic's own passes — what it says after lowering (layouts, VMEM,
  unsupported shape casts). It runs in a subprocess and skips where
  libtpu cannot describe a topology.

Shapes are the serving shapes of record: 8 slots, 16 heads of 64 and
128, page 128; int8 matvecs k1024 → n3072/4096/32768 at 8 decode rows;
the routed experts' streaming kernel at 256 rows over 256 experts of
2048 x 768 and over 32 of 2048 x 1792, and both expert kernels over 16
of 4096 x 4096 in slices of their inner width; the dense slab's attend at 16 slots, 16 heads of 64 and
lanes of 1,024, and at 48 slots of 128 query heads over 8 K/V heads of 128 (a ring of 4,096 and
rows of 8,704), with the ring's block write; the retention state's
step at 16 slots of 8 K/V heads.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SLOTS, HEADS, PAGE, POOL, PAGES_PER_SLOT = 8, 16, 128, 49, 6


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def kernel_cases():
    """[(name, fn, abstract args)] — every kernel behind a TPU gate."""
    from veles_tpu.ops import attention, paged_attention, quant

    table = _sds((SLOTS, PAGES_PER_SLOT), "int32")
    lengths = _sds((SLOTS,), "int32")
    cases = []
    for head_dim in (64, 128):
        for dtype in ("bfloat16", "float32"):
            q = _sds((SLOTS, HEADS, head_dim), dtype)
            pool = _sds((POOL, PAGE, HEADS, head_dim), dtype)
            cases.append((
                "paged_attend_d%d_%s" % (head_dim, dtype),
                lambda q, k, v, pt, ln: paged_attention.paged_attend(
                    q, k, v, pt, ln, page_size=PAGE, interpret=False),
                (q, pool, pool, table, lengths)))
        q = _sds((SLOTS, HEADS, head_dim), "bfloat16")
        pool = _sds((POOL, HEADS, head_dim, PAGE), "int8")
        scale = _sds((POOL, HEADS, PAGE), "float32")
        cases.append((
            "paged_attend_int8_d%d" % head_dim,
            lambda q, k, ks, v, vs, pt, ln:
                paged_attention.paged_attend_int8(
                    q, k, ks, v, vs, pt, ln, page_size=PAGE,
                    interpret=False),
            (q, pool, scale, pool, scale, table, lengths)))
    for n in (3072, 4096, 32768):
        cases.append((
            "int8_matmul_k1024_n%d" % n,
            lambda x, q8, s: quant.int8_matmul(x, q8, s, use_pallas=True),
            (_sds((8, 1024), "bfloat16"), _sds((1024, n), "int8"),
             _sds((n,), "float32"))))
    # flash attention exactly at the use_flash rule's edge (T >= 4096,
    # head_dim % 128 == 0); the tracing process is on the CPU, so the
    # rule's platform is steered for the length of the trace
    qkv = _sds((1, 4096, 2, 128), "bfloat16")

    def flash(q, k, v):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(attention, "on_tpu", lambda: True)
            return attention.attention(q, k, v, causal=True)

    cases.append(("flash_attention_t4096_d128", flash, (qkv, qkv, qkv)))
    # the routed experts' streaming kernel at the published shapes of
    # the benchmark's expert model: a decode step of 32 slots x top-8
    # = 256 rows over 256 experts of 2048 x 768
    from veles_tpu.ops import moe

    # and of its second: 64 slots x top-4 = 256 rows over 32 experts of
    # 2048 x 1792, 22 MB each, two of them in VMEM at once
    rows, width = 256, 2048
    for count, inner in ((256, 768), (32, 1792)):
        cases.append((
            "moe_streamed_experts_r256_e%d_2048x%d" % (count, inner),
            lambda r, load, gate, up, down: moe.streamed_experts(
                r, moe.visit_table(load, rows),
                {"w_gate": gate, "w_up": up, "w_down": down},
                interpret=False),
            (_sds((rows, width), "bfloat16"), _sds((count,), "int32"),
             _sds((count, width, inner), "bfloat16"),
             _sds((count, width, inner), "bfloat16"),
             _sds((count, inner, width), "bfloat16"))))
    # the tiled kernel at the largest part of an admission of each: 4
    # prompts of 1,024 tokens x top-8 and x top-4; and at the smallest
    # that takes it
    for count, inner, many in ((256, 768, 32768), (256, 768, 1024),
                               (32, 1792, 16384)):
        cases.append((
            "moe_tiled_experts_r%d_e%d_2048x%d" % (many, count, inner),
            lambda r, load, gate, up, down: moe.tiled_experts(
                r, moe.tile_table(load, r.shape[0]),
                {"w_gate": gate, "w_up": up, "w_down": down},
                interpret=False),
            (_sds((many, width), "bfloat16"), _sds((count,), "int32"),
             _sds((count, width, inner), "bfloat16"),
             _sds((count, width, inner), "bfloat16"),
             _sds((count, inner, width), "bfloat16"))))
    # and over the 16 held experts of 4096 x 4096 of the window cell,
    # which the kernels take in slices of their inner width (the rule's
    # for a v5e): a decode step of 48 slots x top-8 = 384 rows, and the
    # largest part of an admission, 8,192 tokens x top-8
    for many in (384, 65536):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe, "on_tpu", lambda: True)
            patch.setattr(moe, "device_kind", lambda: "TPU v5 lite")
            path, tile, sliced = moe.expert_plan(
                many, {"w_gate": _sds((16, 4096, 4096), "bfloat16")})
        if path == "streamed":
            call = (lambda r, load, gate, up, down, sliced=sliced:
                    moe.streamed_experts(
                        r, moe.visit_table(load, r.shape[0]),
                        {"w_gate": gate, "w_up": up, "w_down": down},
                        sliced, interpret=False))
        else:
            call = (lambda r, load, gate, up, down, tile=tile,
                    sliced=sliced: moe.tiled_experts(
                        r, moe.tile_table(load, r.shape[0], tile),
                        {"w_gate": gate, "w_up": up, "w_down": down},
                        tile, sliced, interpret=False))
        cases.append((
            "moe_%s_experts_r%d_e16_4096x4096_f%d" % (path, many, sliced),
            call,
            (_sds((many, 4096), "bfloat16"), _sds((16,), "int32"),
             _sds((16, 4096, 4096), "bfloat16"),
             _sds((16, 4096, 4096), "bfloat16"),
             _sds((16, 4096, 4096), "bfloat16"))))
    # the dense slab's ragged-length attend at the serving cell's
    # shapes: 16 slots, 16 heads of 64, lanes of 1,024, a span of 896
    from veles_tpu.ops import slab_attention

    def slab(q, k, v, lengths):
        # (with the VMEM claim the chip's kind gives: 100 of 128 MiB)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(slab_attention, "device_kind",
                          lambda: "TPU v5 lite")
            return slab_attention.slab_attend(q, k, v, lengths, 896,
                                              interpret=False)

    for dtype in ("bfloat16", "float32"):
        leaf = _sds((16, HEADS * 64, 1024), dtype)
        cases.append((
            "slab_attend_s16_h16_d64_t1024_%s" % dtype, slab,
            (_sds((16, 1, HEADS, 64), dtype), leaf, leaf,
             _sds((16,), "int32"))))

    # and of grouped heads, the window cell's: 48 slots, 128 query heads
    # over 8 K/V heads of 128, a ring of 4,096 and rows to 8,704
    def grouped(q, k, v, lengths, *ring):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(slab_attention, "device_kind",
                          lambda: "TPU v5 lite")
            return slab_attention.slab_attend(
                q, k, v, lengths, k.shape[-1], interpret=False,
                ring=ring or None)

    for name, length, ring in (("ring", 4096, 2), ("rows", 8704, 0)):
        leaf = _sds((48, 8 * 128, length), "bfloat16")
        cases.append((
            "slab_attend_%s_s48_h128_g8_d128_t%d" % (name, length), grouped,
            (_sds((48, 1, 128, 128), "bfloat16"), leaf, leaf)
            + (_sds((48,), "int32"),) * (1 + ring)))
    # the chunk's block write at the serving cells' leaves (two leaves
    # a call: the kernel unrolls a leaf's code, the count only repeats
    # it): GPT-2's k/v, JoyAI's latent kv, LFM2's grouped k/v
    from veles_tpu.ops import slab_write

    def write(before, leaves, staged, rings=None):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(slab_write, "device_kind", lambda: "TPU v5 lite")
            return slab_write.write_blocks(leaves, staged, before,
                                           interpret=False, rings=rings)

    for slots, width, max_len in ((16, 1024, 1024), (32, 576, 2048),
                                  (64, 512, 2048)):
        for dtype in ("bfloat16", "float32"):
            cases.append((
                "slab_write_s%d_w%d_t%d_%s" % (slots, width, max_len,
                                               dtype), write,
                (_sds((slots,), "int32"),
                 [_sds((slots, width, max_len), dtype)] * 2,
                 [_sds((slots, width, 8), dtype)] * 2)))
    # the window cell's rings: a block written at the length modulo the
    # ring, split where it wraps
    cases.append((
        "slab_write_ring_s48_w1024_t4096_bfloat16",
        lambda before, leaves, staged: write(before, leaves, staged,
                                             rings=[True, True]),
        (_sds((48,), "int32"), [_sds((48, 1024, 4096), "bfloat16")] * 2,
         [_sds((48, 1024, 8), "bfloat16")] * 2)))
    # the retention state's decode step at the serving cell's shapes:
    # 16 slots, 8 K/V heads of 128 with five query heads each, 8,320
    # products a head, float32
    from veles_tpu.ops import retention

    wide = retention.features(128)
    cases.append((
        "retention_step_s16_g8_r5_d128",
        lambda small, held, norm: retention._step_call(
            small, held, norm, interpret=False, claim=64 << 20),
        (_sds((16, 8, 5 + 3, 128), "float32"),
         _sds((16, 8, 128, wide), "float32"),
         _sds((16, 8, wide), "float32"))))
    return cases


@pytest.mark.paged_kernel
@pytest.mark.parametrize("case", kernel_cases(), ids=lambda c: c[0])
def test_kernel_lowers_for_tpu(case):
    _, fn, args = case
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


_COMPILE_CHILD = """
import sys
sys.path.insert(0, %(repo)r)
sys.path.insert(0, %(tests)r)
import jax
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as exc:
    print("NO-TOPOLOGY %%s" %% exc)
    sys.exit(0)
print("KIND %%s" %% topo.devices[0].device_kind)
on_chip = SingleDeviceSharding(topo.devices[0])
import test_tpu_lowering
for name, fn, args in test_tpu_lowering.kernel_cases():
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        args)
    try:
        jax.jit(fn).lower(*args).compile()
        print("COMPILED %%s" %% name)
    except Exception as exc:
        print("REFUSED %%s: %%s" %% (name, str(exc)[:800].replace("\\n", " | ")))
"""


@pytest.mark.paged_kernel
def test_kernels_compile_for_v5e_without_a_chip():
    """The full Mosaic pipeline, offline: AOT-compile every case for a
    compile-only v5e topology. Skips where libtpu cannot describe one."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _COMPILE_CHILD % {
                "repo": REPO, "tests": os.path.join(REPO, "tests")}],
            env=env, capture_output=True, text=True, timeout=240)
    except subprocess.TimeoutExpired:
        pytest.skip("the compile-only TPU client did not answer here")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    if any(line.startswith("NO-TOPOLOGY") for line in lines):
        pytest.skip("no compile-only TPU topology here: %s" % lines[0])
    refused = [line for line in lines if line.startswith("REFUSED")]
    compiled = [line for line in lines if line.startswith("COMPILED")]
    assert not refused, "\n".join(refused)
    assert len(compiled) == len(kernel_cases())


# -- the decode chunk program uses the K/V slab in place ----------------------

_CHUNK_CHILD = """
import functools, json, re, sys
sys.path.insert(0, %(repo)r)
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as exc:
    print("NO-TOPOLOGY %%s" %% exc)
    sys.exit(0)
jax.config.update("jax_enable_compilation_cache", False)
# this process sees the CPU: the rule and the kernel are told that the
# program compiled here is the chip's
from veles_tpu.ops import slab_attention
slab_attention.on_tpu = lambda: True
slab_attention.device_kind = lambda: topo.devices[0].device_kind
slab_attention.pallas_interpret = lambda: False
from veles_tpu.ops import slab_write
slab_write.on_tpu = lambda: True
slab_write.device_kind = lambda: topo.devices[0].device_kind
slab_write.pallas_interpret = lambda: False
from veles_tpu.parallel import decode
chip = SingleDeviceSharding(topo.devices[0])
E, HEADS, LAYERS, HIDDEN, VOCAB, MAX_LEN, SLOTS = %(sizes)r
bf = jnp.bfloat16
def spec(shape, dtype=bf):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
block = {"ln1_w": spec((E,)), "ln1_b": spec((E,)),
         "wqkv": spec((E, 3 * E)), "bqkv": spec((3 * E,)),
         "wout": spec((E, E)), "bout": spec((E,)),
         "ln2_w": spec((E,)), "ln2_b": spec((E,)),
         "w1": spec((E, HIDDEN)), "b1": spec((HIDDEN,)),
         "w2": spec((HIDDEN, E)), "b2": spec((E,))}
params = {"blocks": [block] * LAYERS, "lnf_w": spec((E,)),
          "lnf_b": spec((E,)), "head": spec((E, VOCAB))}
table = spec((VOCAB, E))
state = jax.tree.map(
    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
    jax.eval_shape(functools.partial(
        decode.init_slot_state, LAYERS, SLOTS, MAX_LEN, HEADS,
        E // HEADS, VOCAB, dtype=bf)))
formats = decode.decide_slot_formats(params, table, HEADS, state, 8, 512)
place = dict(formats)
place.update(dict.fromkeys(("lengths", "logits", "req_key", "step")))
chunk = decode._build_slot_fns(place)[2].__wrapped__
compiled = chunk.lower(
    params, table, HEADS, state, spec((SLOTS,), jnp.bool_), 8,
    spec((), jnp.float32), False, 0, 512).compile()
text = compiled.as_text()
leaf = state["k"][0]
shape = ",".join(str(n) for n in leaf.shape)
# an op of its own whose result is a whole K/V leaf, other than the
# in-place appends: a copy of a layer
whole = [line.strip()[:200] for line in text.splitlines()
         if re.search(r"= bf16\\[%%s\\]\\S* (copy|fusion)\\(" %% shape, line)
         and "dynamic-update-slice" not in line.split(" = ")[0]]
# a float32 value of the attended window's size: every slot's K or V
# widened at the span
window = [line.strip()[:200] for line in text.splitlines()
          if re.search(r"= f32\\[%%d,%%d,512\\]" %% leaf.shape[:2], line)]
# a whole leaf copied into VMEM ahead of its use (memory-space
# assignment's prefetch of a kernel's operand)
prefetched = [line.strip()[:200] for line in text.splitlines()
              if " copy-start(" in line
              and line.split(" = ")[1].startswith("(bf16[%%s]" %% shape)]
print("RESULT " + json.dumps({
    "slab_attend_calls": len(re.findall(
        r"%%slab_attend\\S* = .*custom_call_target=.tpu_custom_call", text)),
    "slab_write_calls": len(re.findall(
        r"%%slab_write\\S* = .*custom_call_target=.tpu_custom_call", text)),
    # loops: the scan over the chunk's steps, and any other (a loop
    # over the slots of per-slot appends would be one a leaf)
    "whiles": text.count(" while("),
    "leaf_prefetches": prefetched,
    "custom_calls": text.count('custom_call_target="tpu_custom_call"'),
    "widened_windows": window,
    "layout": [str(formats[name].layout) for name in ("k", "v")],
    "padded_bytes": compiled.memory_analysis().argument_size_in_bytes,
    "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
    "slab_bytes": 2 * LAYERS * leaf.size * 2,
    "weights_bytes": sum(a.size * 2 for a in jax.tree.leaves(params))
                     + table.size * 2,
    "whole_leaf_ops": whole,
    "remat_uncompressed": text.count("remat_uncompressed = ")}))
"""


@pytest.mark.parametrize("slots", [16, 32])
def test_chunk_program_uses_the_slab_in_place_on_v5e(slots):
    """Compiled for a described v5e at gpt2-medium's sizes and 16 slots
    (the benchmark's serving cell; and 32, the size a re-sized cell
    would hold: no whole-leaf copy there either, where
    ``benchmark/configs/gpt2-medium.json`` ``assumed`` records 115 of an
    older program), the chunk program of
    ``slot_step_many`` with the layout the decoder would pin: it holds
    the ragged-length attend kernel once a block, the block write's
    kernel once (and no loop of per-slot writes), no float32 window
    of every slot at the span, its temporaries stay under 5% of the
    slab, no op of its own produces a whole K/V leaf (a copy of a
    layer, which a kernel's operand in another layout would cost) and
    the slab's arguments are its bytes, unpadded. Skips where no TPU
    compiler is installed."""
    import json

    sizes = (1024, 16, 24, 4096, 50257, 1024, slots)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             _CHUNK_CHILD % {"repo": REPO, "sizes": sizes}],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        pytest.skip("the compile-only TPU client did not answer here")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    if any(line.startswith("NO-TOPOLOGY") for line in lines):
        pytest.skip("no compile-only TPU topology here: %s" % lines[0])
    (result,) = [json.loads(line[len("RESULT "):]) for line in lines
                 if line.startswith("RESULT ")]
    slab = result["slab_bytes"]
    layers = sizes[2]
    assert result["slab_attend_calls"] == layers, result
    # the chunk's blocks of all 48 leaves: one call, and no loop of
    # per-slot writes beside the scan over the steps
    assert result["slab_write_calls"] == 1, result
    assert result["custom_calls"] == layers + 1, result
    assert result["whiles"] == 1, result
    assert not result["leaf_prefetches"], result["leaf_prefetches"][:3]
    assert not result["widened_windows"], result["widened_windows"][:3]
    assert result["temp_bytes"] < 0.05 * slab, result
    assert not result["whole_leaf_ops"], result["whole_leaf_ops"][:3]
    assert not result["remat_uncompressed"]
    # nothing pads the slab: the arguments are the weights, the slab
    # and the small control leaves (logits: 16 x 50257 f32)
    assert result["padded_bytes"] < slab + result["weights_bytes"] \
        + 0.01 * slab, result


# -- the routed experts: streamed in the chunk, tiled in an admission ---------

_EXPERTS_CHILD = """
import json, os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, %(repo)r)
sys.path.insert(0, os.path.join(%(repo)r, "benchmark", "tools"))
from jax.experimental import topologies
try:
    topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
except Exception as exc:
    print("NO-TOPOLOGY %%s" %% exc)
    sys.exit(0)
# this process sees the CPU: the rule and the kernel are told that the
# programs compiled here are the chip's, a v5e's
from veles_tpu.ops import moe
moe.on_tpu = lambda: True
moe.pallas_interpret = lambda: False
moe.device_kind = lambda: "TPU v5 lite"
import offchip_compile_arch
with open(os.path.join(
        %(repo)r, "benchmark/configs/%(config)s.json")) as fin:
    config = json.load(fin)
offchip_compile_arch.serve_programs(config, %(programs)r, %(out)r)
"""


def _compile_off_the_chip(config, programs, out):
    """``benchmark/tools/offchip_compile_arch.py`` over ``programs`` of
    the configuration ``config`` in a child that tells ``ops/moe`` it
    is the chip's: ``{program: what the tool said of it}``, the
    compiled texts under ``out``."""
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _EXPERTS_CHILD % {
                "repo": REPO, "config": config, "programs": programs,
                "out": str(out)}],
            env=env, capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        pytest.skip("the compile-only TPU client did not answer here")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    if any(line.startswith("NO-TOPOLOGY") for line in lines):
        pytest.skip("no compile-only TPU topology here: %s" % lines[0])
    return {row["program"]: row for row in (
        json.loads(line) for line in lines if line.startswith("{"))}


def test_chunk_streams_the_experts_and_an_admission_tiles_them_on_v5e(
        tmp_path):
    """The benchmark's expert model at its published widths, compiled
    for a described v5e: the chunk program (32 slots x top-8 = 256
    rows an expert layer) holds the streaming kernel, once an expert
    layer, and no ``ragged-dot`` custom call; the smallest admission
    (one prompt of 128 tokens: 1,024 rows) holds the tiled kernel once
    an expert layer and neither of the others. Neither program's
    temporaries come near one expert layer's matrices: no copy of the
    experts."""
    said = _compile_off_the_chip("joyai-llm-flash",
                                 ["step:8:1408", "admit:128:1"], tmp_path)
    chunk = (tmp_path / "step_8_1408.txt").read_text()
    admit = (tmp_path / "admit_128_1.txt").read_text()
    expert_layers = 4
    assert "ragged-dot" not in chunk
    # (the compiler's grouped kernel is a tpu_custom_call too)
    assert chunk.count('custom_call_target="tpu_custom_call"') \
        == expert_layers
    assert chunk.count(
        'mlp/moe.experts/moe_streamed_experts/pallas_call"') \
        == expert_layers
    assert "moe_streamed_experts" not in admit
    assert "ragged-dot" not in admit
    assert admit.count('custom_call_target="tpu_custom_call"') \
        == expert_layers
    assert admit.count(
        'mlp/moe.experts/jit(_tiled)/moe.experts/'
        'moe_tiled_experts/pallas_call"') \
        == expert_layers
    one_layer = 3 * 256 * 2048 * 768 * 2
    assert said["step:8:1408"]["temp_bytes"] < 0.1 * one_layer, said
    assert said["admit:128:1"]["temp_bytes"] < 0.1 * one_layer, said


def test_a_kind_per_block_compiles_for_v5e_at_published_widths(tmp_path):
    """The benchmark's model with a kind for each block (10 gated
    short convolutions, 3 grouped-query attentions, 12 layers of 32
    experts of 2048 x 1792) at its serving shape, compiled for a
    described v5e: the chunk program over the whole lane (64 slots x
    top-4 = 256 rows an expert layer) holds the streaming kernel once
    an expert layer and nothing else of its kind; the slab's leaves
    are those of the three attention blocks alone and the chunk copies
    none of them; the tied head reads the embedding table where it
    lies; an admission of four prompts of 1,024 tokens (512 rows an
    expert) holds the tiled kernel once an expert layer."""
    said = _compile_off_the_chip(
        "lfm2-8b-a1b", ["step:8:2048", "admit:1024:4"], tmp_path)
    chunk = (tmp_path / "step_8_2048.txt").read_text()
    admit = (tmp_path / "admit_1024_4.txt").read_text()
    expert_layers = 12
    assert "ragged-dot" not in chunk
    assert chunk.count('custom_call_target="tpu_custom_call"') \
        == expert_layers
    assert chunk.count(
        'mlp/moe.experts/moe_streamed_experts/pallas_call"') \
        == expert_layers
    assert "moe_streamed_experts" not in admit
    assert "ragged-dot" not in admit
    assert admit.count(
        'mlp/moe.experts/jit(_tiled)/moe.experts/'
        'moe_tiled_experts/pallas_call"') \
        == expert_layers
    for scope in ("attn.qkv/conv.in", "attn.attend/conv.mix",
                  "attn.out/conv.out", "cache.append/cache.state",
                  "attn.qkv/gqa.norm", "attn.qkv/gqa.rope"):
        assert scope in chunk, scope
    # K and V of 8 heads x 64 over 2,048 positions in three blocks:
    # what the program takes and gives back in place
    slab = 3 * 2 * 64 * 512 * 2048 * 2
    step = said["step:8:2048"]
    assert slab < step["alias_bytes"] < 1.05 * slab, step
    assert step["remat_uncompressed_copies"] == 0
    # no second copy of the table stands as the head
    assert not [line for line in chunk.splitlines()
                if " copy(" in line and "bf16[65536,2048]" in
                line.split(" = ")[1].split(" copy(")[0]]
    one_layer = 3 * 32 * 2048 * 1792 * 2
    assert step["temp_bytes"] < 0.15 * one_layer, step
    weights = 4606249728 * 2
    for row in said.values():
        assert row["argument_bytes"] + row["temp_bytes"] < 15.75e9, row
        assert row["argument_bytes"] > weights + slab


# -- a model with no row a position: the state held once -----------------------

_RETENTION_CHILD = """
import functools, json, os, re, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, %(repo)r)
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as exc:
    print("NO-TOPOLOGY %%s" %% exc)
    sys.exit(0)
jax.config.update("jax_enable_compilation_cache", False)
# this process sees the CPU: the rule and the kernel are told that the
# programs compiled here are the chip's
from veles_tpu.ops import retention
retention.on_tpu = lambda: True
retention.device_kind = lambda: topo.devices[0].device_kind
retention.pallas_interpret = lambda: False
from benchmark.harness import common
from veles_tpu.parallel import blocks, decode
chip = SingleDeviceSharding(topo.devices[0])
config = common.load_json("benchmark/configs/brumby-14b-base.json")
reference = common.load_module(config["reference"])
serving = config["serving"]
heads, slots = config["n_head"], serving["slots"]
def on_chip(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=chip), tree)
def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
params, table = jax.eval_shape(
    functools.partial(reference.init_params, 0, config))
arch = blocks.arch_of(params)
params, table = on_chip((params, table))
e, v = table.shape[1], table.shape[0]
state = on_chip(jax.eval_shape(functools.partial(
    decode.init_slot_state, len(params["blocks"]), slots,
    serving["max_len"], heads, e // heads, v, dtype=table.dtype,
    arch=arch)))
# the place as decode.slot_fns pins it for a state without K/V leaves
place = dict.fromkeys(decode.CONTROL_LEAVES + (decode.FIXED,), chip)
admit, _, chunk = decode._build_slot_fns(place)
leaf = state[decode.FIXED]["S"][0]
shape = ",".join(str(n) for n in leaf.shape)
out = {"state_bytes": sum(
    a.size * 4 for a in jax.tree.leaves(state[decode.FIXED]))}
for name, lowered in (
        ("step", chunk.__wrapped__.lower(
            params, table, heads, state, spec((slots,), jnp.bool_), 8,
            spec((), jnp.float32), False, 0, 0)),
        ("admit", admit.__wrapped__.lower(
            params, table, heads, state, spec((16,), jnp.int32),
            spec((16, 1024, e), table.dtype),
            on_chip(jax.eval_shape(
                lambda: jax.random.split(jax.random.key(0), 16))),
            spec((16,), jnp.int32)))):
    compiled = lowered.compile()
    memory, text = compiled.memory_analysis(), compiled.as_text()
    out[name] = {
        "temp": memory.temp_size_in_bytes,
        "arguments": memory.argument_size_in_bytes,
        "alias": memory.alias_size_in_bytes,
        "kernels": len(re.findall(
            r"%%retention_step\\S* = .*custom_call_target=.tpu_custom_call",
            text)),
        # a whole state leaf made by a copy, or by a fusion that is no
        # row's write in place: the state held twice
        "whole_leaf_ops": [
            line.strip()[:160] for line in text.splitlines()
            if re.search(r"= f32\\[%%s\\]\\S* (copy|fusion)\\(" %% shape, line)
            and "dynamic-update-slice" not in line.split(" = ")[0]]}
print("RESULT " + json.dumps(out))
"""


def test_the_retention_state_is_held_once_on_v5e():
    """Compiled for a described v5e at the serving cell's sizes
    (``brumby-14b-base``: 8 layers, 16 slots, 4.4 GB of float32 state
    beside 8.4 GB of weights), with the place pinned as the decoder
    pins it: the chunk program holds the state's kernel once a layer
    and every state leaf is the result's buffer (donated, carried
    through the scan, aliased: no copy at its entry or exit); the
    largest admission writes each admitted row where the leaf lies (no
    op of its own makes a whole leaf) and, with its temporaries, fits
    a v5e's 16 GiB beside the embedding table. Skips where no TPU
    compiler is installed."""
    import json

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _RETENTION_CHILD % {"repo": REPO}],
            env=env, capture_output=True, text=True, timeout=600,
            cwd=REPO)
    except subprocess.TimeoutExpired:
        pytest.skip("the compile-only TPU client did not answer here")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    if any(line.startswith("NO-TOPOLOGY") for line in lines):
        pytest.skip("no compile-only TPU topology here: %s" % lines[0])
    (result,) = [json.loads(line[len("RESULT "):]) for line in lines
                 if line.startswith("RESULT ")]
    state = result["state_bytes"]
    step, admit = result["step"], result["admit"]
    assert step["kernels"] == 8, result
    # every state leaf aliased (and the control leaves with them)
    assert step["alias"] >= state and admit["alias"] >= state, result
    assert not step["whole_leaf_ops"], step["whole_leaf_ops"][:3]
    assert not admit["whole_leaf_ops"], admit["whole_leaf_ops"][:3]
    assert step["temp"] < 0.25 * state, result
    # weights + state (the arguments), the admission's temporaries and
    # the embedding table it does not take: under the allocator's limit
    table = 151936 * 5120 * 2
    assert admit["arguments"] + admit["temp"] + table < 16.9e9, result


def test_the_window_cells_experts_take_the_kernels_in_slices_on_v5e(
        tmp_path):
    """Command A+'s held experts (16 of 4096 x 4096, two whole ones
    past the VMEM) compiled for a described v5e: the chunk program over
    every slot of the smallest span (48 slots x top-8 = 384 rows an
    expert layer) holds the sliced streaming kernel once an expert
    layer, in its own jit and under the experts' scope, and no
    ``ragged-dot``; an admission of one prompt of 256 tokens (2,048
    rows) holds the sliced tiled kernel once an expert layer and
    neither of the others; no program copies the held experts."""
    said = _compile_off_the_chip("command-a-plus-05-2026",
                                 ["step:8:640", "admit:256:1"], tmp_path)
    chunk = (tmp_path / "step_8_640.txt").read_text()
    admit = (tmp_path / "admit_256_1.txt").read_text()
    expert_layers = 4
    assert "ragged-dot" not in chunk and "ragged-dot" not in admit
    assert chunk.count(
        'mlp/moe.experts/jit(_streamed)/moe.experts/'
        'moe_streamed_experts/pallas_call"') == expert_layers
    assert "moe_tiled_experts" not in chunk
    assert "moe_streamed_experts" not in admit
    assert admit.count(
        'mlp/moe.experts/jit(_tiled)/moe.experts/'
        'moe_tiled_experts/pallas_call"') \
        == expert_layers
    held = 16 * 3 * 4096 * 4096 * 2
    for row in said.values():
        assert row["temp_bytes"] < 0.6 * held, row
        assert row["ragged_dots"] == 0, row
