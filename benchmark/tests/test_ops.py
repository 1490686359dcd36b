"""The operation and byte counts against the hand counts stated in
their files."""

import pytest

from benchmark.harness import common

ALEXNET = common.load_json("benchmark/configs/alexnet-227.json")
GPT2 = common.load_json("benchmark/configs/gpt2-medium.json")
alexnet = common.load_module("benchmark/ops/alexnet.py")
gpt2 = common.load_module("benchmark/ops/gpt2.py")


def test_alexnet_hand_counts():
    assert alexnet.forward_macs(ALEXNET) == alexnet.HAND_FORWARD_MACS \
        == 1176854048
    assert alexnet.train_ops_per_image(ALEXNET) \
        == alexnet.HAND_TRAIN_OPS == 6834681984
    assert [c["macs"] for c in alexnet.conv_layers(ALEXNET)] == [
        113221152, 481689600, 149520384, 224280576, 149520384]
    assert alexnet.dense_layers(ALEXNET) == [
        (9216, 4096), (4096, 4096), (4096, 1000)]


@pytest.mark.parametrize("shapes, want", [
    ([("bf16", (128, 57, 57, 96)), ("bf16", (128, 227, 227, 3)),
      ("bf16", (11, 11, 3, 96))], (0, "forward")),
    ([("f32", (11, 11, 3, 96)), ("bf16", (128, 227, 227, 3)),
      ("bf16", (128, 57, 57, 96))], (0, "weight_grad")),
    ([("bf16", (128, 28, 28, 96)), ("bf16", (128, 28, 28, 256)),
      ("bf16", (5, 5, 96, 256))], (1, "input_grad")),
    ([("f32", (128, 28, 28, 256)), ("f32", (128, 28, 28, 256))], None),
    ([("f32", (9216, 4096)), ("f32", (9216, 4096))], None),
])
def test_conv_role(shapes, want):
    assert alexnet.conv_role(ALEXNET, 128, shapes) == want


def test_conv_ops_and_bytes():
    shapes = [("bf16", (128, 57, 57, 96)), ("bf16", (128, 227, 227, 3)),
              ("bf16", (11, 11, 3, 96))]
    ops, nbytes = alexnet.conv_ops_and_bytes(ALEXNET, 128, 0, shapes)
    assert ops == 2 * 128 * 113221152
    assert nbytes == 2 * (128 * 227 * 227 * 3 + 128 * 57 * 57 * 96
                          + 11 * 11 * 3 * 96)


def test_gpt2_hand_counts():
    assert gpt2.kv_bytes_per_position(GPT2) \
        == gpt2.HAND_KV_BYTES_PER_POSITION == 96 * 1024
    assert gpt2.matrix_parameters(GPT2) \
        == gpt2.HAND_MATRIX_PARAMETERS == 353453056
    ops, nbytes = gpt2.decode_step(GPT2, [599] * 32)
    assert nbytes == 353453056 * 2 + 98304 * 600 * 32
    assert ops == 2 * 353453056 * 32 + 4 * 24 * 1024 * 600 * 32
    one = gpt2.prefill(GPT2, [256])
    assert one == (2 * 301989888 * 256 + 4 * 24 * 1024 * 256 * 257 // 2
                   + 2 * 1024 * 50257)
