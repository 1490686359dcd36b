"""The chunk's block write to the dense slab and the rule that takes it.

``ops/slab_write.write_blocks`` (one Pallas call: a read-modify-write
of the 128-lane pieces that hold each slot's block) in interpret mode
against ``write_blocks_loop`` (a ``dynamic_update_slice`` per slot and
leaf, the path everything else keeps). "Agrees" means bit for bit:
the kernel moves the staged columns' words and sends every other lane
back as it came. The tests steer the platform (``slab_write.on_tpu``
and ``device_kind``), never the path.
"""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.ops import slab_write
from veles_tpu.parallel import decode

MAX_LEN = 256
#: 0 and 1, a piece's edge - 8 (a block of 8 ends on it), - 1, the
#: edge, + 1, the last block that fits (T - n) and past it (T - 1,
#: clamped), and an idle lane's length far past the lane's end
LENGTHS = (0, 1, 119, 120, 127, 128, 129, "T-n", MAX_LEN - 1, 900)


def _operands(dtype, width, n, leaves=2, seed=0):
    rng = numpy.random.RandomState(seed)
    before = jnp.asarray([MAX_LEN - n if at == "T-n" else at
                          for at in LENGTHS], jnp.int32)
    slots = before.shape[0]
    return ([jnp.asarray(rng.randn(slots, width, MAX_LEN), dtype)
             for _ in range(leaves)],
            [jnp.asarray(rng.randn(slots, width, n), dtype)
             for _ in range(leaves)], before)


def _bits(leaves):
    return [numpy.asarray(leaf).view(numpy.uint8) for leaf in leaves]


def _apart(one, other):
    """Leaves whose bits differ anywhere."""
    return sum(not numpy.array_equal(a, b)
               for a, b in zip(_bits(one), _bits(other)))


@pytest.mark.parametrize("n", [1, 8])
@pytest.mark.parametrize("width", [1024, 576, 512],
                         ids=lambda w: "rows%d" % w)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_kernel_writes_the_loops_bits(dtype, width, n):
    """At the rows of GPT-2's, JoyAI's and LFM2's leaves: every length
    above, a block that straddles a piece's boundary among them, and
    the clamp onto the lane's end."""
    leaves, staged, before = _operands(dtype, width, n)
    got = slab_write.write_blocks(leaves, staged, before)
    want = slab_write.write_blocks_loop(leaves, staged, before)
    assert [leaf.shape for leaf in got] == [leaf.shape for leaf in want]
    assert _apart(got, want) == 0
    # (and the write is not nothing)
    assert _apart(got, leaves) == len(leaves)


@pytest.mark.parametrize("fault", ["start_off_by_one",
                                   "second_piece_dropped",
                                   "clamp_left_out"])
def test_a_planted_fault_breaks_the_agreement(fault, monkeypatch):
    """The agreement above catches a block one lane late, the second
    piece of a block that straddles two left unwritten, and a block
    past the lane's end not clamped back onto it."""
    start = slab_write._block_start
    if fault == "start_off_by_one":
        monkeypatch.setattr(slab_write, "_block_start",
                            lambda before, max_len, n:
                                start(before, max_len, n) + 1)
    elif fault == "second_piece_dropped":
        monkeypatch.setattr(slab_write, "_straddles",
                            lambda off, n: off < 0)
    else:
        monkeypatch.setattr(slab_write, "_block_start",
                            lambda before, max_len, n:
                                jnp.maximum(before, 0))
    jax.clear_caches()
    try:
        leaves, staged, before = _operands("float32", 512, 8)
        if fault == "clamp_left_out":
            # (a block past the end: the second piece would start at
            # the lane's end itself)
            keep = jnp.asarray([at >= MAX_LEN - 8 for at in before])
            before = jnp.where(keep, before, MAX_LEN - 8)
        got = slab_write.write_blocks(leaves, staged, before)
        want = slab_write.write_blocks_loop(leaves, staged, before)
        assert _apart(got, want) == len(leaves)
    finally:
        jax.clear_caches()


# -- the rule -------------------------------------------------------------------

def _leaf(shape=(16, 1024, 1024), dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _one_device():
    return jax.sharding.SingleDeviceSharding(jax.devices()[0])


def _steer(monkeypatch, on_tpu=True, kind="TPU v5 lite"):
    monkeypatch.setattr(slab_write, "on_tpu", lambda: on_tpu)
    monkeypatch.setattr(slab_write, "device_kind", lambda: kind)


RULE_CASES = {
    # what the rule reads: (leaf, n, on the TPU, its kind, the answer)
    "platform_cpu": (_leaf(), 8, False, "cpu", False),
    "gpt2_k_v": (_leaf(), 8, True, "TPU v5 lite", True),
    "joyai_kv": (_leaf((32, 576, 2048)), 8, True, "TPU v5 lite", True),
    "lfm2_k_v": (_leaf((64, 512, 2048)), 8, True, "TPU v5 lite", True),
    "float32": (_leaf(dtype=jnp.float32), 8, True, "TPU v5 lite", True),
    "one_step": (_leaf(), 1, True, "TPU v5 lite", True),
    "n_a_whole_piece": (_leaf(), 128, True, "TPU v5 lite", True),
    "n_past_a_piece": (_leaf(), 129, True, "TPU v5 lite", False),
    "vmem_unknown_kind": (_leaf(), 8, True, "TPU v9", False),
    "int8_kv_tier": (_leaf((16, 16, 64, 1024), jnp.int8), 8, True,
                     "TPU v5 lite", False),
    "int8_dtype": (_leaf(dtype=jnp.int8), 8, True, "TPU v5 lite", False),
    "rows_not_sublane_tiles": (_leaf((16, 40, 1024)), 8, True,
                               "TPU v5 lite", False),
    "rows_float32_tiles": (_leaf((16, 40, 1024), jnp.float32), 8, True,
                           "TPU v5 lite", True),
    "max_len_not_pieces": (_leaf((16, 1024, 1000)), 8, True,
                           "TPU v5 lite", False),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_rule_reads_platform_dtype_shapes_and_n(case, monkeypatch):
    leaf, n, on_tpu, kind, want = RULE_CASES[case]
    _steer(monkeypatch, on_tpu, kind)
    assert slab_write.use_write_kernel(leaf, _one_device(), n) is want


def test_the_rule_reads_the_place(monkeypatch):
    """Leaves on two devices keep the loop (a bare ``pallas_call``
    cannot be partitioned), and so do leaves nobody can place."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    _steer(monkeypatch)
    mesh = jax.sharding.Mesh(numpy.array(jax.devices()[:2]), ("model",))
    leaf = _leaf()
    assert slab_write.use_write_kernel(leaf, _one_device(), 8) is True
    assert slab_write.use_write_kernel(
        leaf, NamedSharding(mesh, P(None, "model")), 8) is False
    assert slab_write.use_write_kernel(
        leaf, NamedSharding(mesh, P()), 8) is False
    assert slab_write.use_write_kernel(leaf, None, 8) is False


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["float", "int8_kv"])
def test_a_state_takes_the_kernel_for_every_leaf_or_for_none(
        quantized, monkeypatch):
    """``decode.block_write_path``: the float slab's ``k`` and ``v``
    take the kernel; the int8-KV tier keeps the loop for its leaves and
    its float32 scales alike (the scales alone would pass the rule)."""
    _steer(monkeypatch)
    state = jax.eval_shape(lambda: decode.init_slot_state(
        2, 16, 1024, 16, 64, 50, dtype=jnp.bfloat16, quantized=quantized))
    assert ("k_scale" in state) is quantized
    if quantized:
        assert slab_write.use_write_kernel(state["k_scale"][0],
                                           _one_device(), 8)
    assert decode.block_write_path(state, _one_device(), 8) == \
        ("loop" if quantized else "kernel")
    assert decode.block_write_path(state, None, 8) == "loop"


def test_the_claim_is_a_share_of_the_chips_vmem(monkeypatch):
    for kind, want in (("TPU v5 lite", 100 << 20), ("TPU v5p", 50 << 20),
                       ("TPU v9", None), ("cpu", None)):
        _steer(monkeypatch, kind=kind)
        assert slab_write.vmem_claim() == want


def test_leaves_of_two_shapes_take_one_call_each(monkeypatch):
    """One call for the leaves of a shape and type, each leaf back in
    its place in the order given."""
    _steer(monkeypatch)
    calls = []
    write = slab_write._write

    def counted(before, leaves, staged, **kwargs):
        calls.append(len(leaves))
        return write(before, leaves, staged, **kwargs)

    monkeypatch.setattr(slab_write, "_write", counted)
    wide, wide_staged, before = _operands("float32", 16, 4, leaves=3)
    narrow, narrow_staged, _ = _operands("float32", 8, 4, leaves=2, seed=1)
    leaves = [wide[0], narrow[0], wide[1], narrow[1], wide[2]]
    staged = [wide_staged[0], narrow_staged[0], wide_staged[1],
              narrow_staged[1], wide_staged[2]]
    got = slab_write.write_blocks(leaves, staged, before)
    assert sorted(calls) == [2, 3]
    assert _apart(got, slab_write.write_blocks_loop(
        leaves, staged, before)) == 0
