"""Operations and bytes the AlexNet configuration needs, from shapes.

Counted from the layer list of the configuration file, whatever
implements the layers. A multiply-add is two operations.

Hand count at the shapes of ``benchmark/configs/alexnet-227.json``
(227x227x3 in, conv1 SAME at stride 4 -> 57x57), multiply-adds per
image, forward:

  conv1 57*57*96 * 11*11*3      = 113,221,152
  conv2 28*28*256 * 5*5*96      = 481,689,600
  conv3 13*13*384 * 3*3*256     = 149,520,384
  conv4 13*13*384 * 3*3*384     = 224,280,576
  conv5 13*13*256 * 3*3*384     = 149,520,384
  fc1   9216*4096               =  37,748,736
  fc2   4096*4096               =  16,777,216
  fc3   4096*1000               =   4,096,000
  total                         = 1,176,854,048  (1.18 G, as ISSUE 26)

Backward needs the same again for every weight gradient and for every
input gradient except conv1's (nothing upstream needs it), so a train
step needs 3 x 1,176,854,048 - 113,221,152 = 3,417,340,992
multiply-adds = 6,834,681,984 operations an image ("6.8 GFLOP").
Elementwise work (bias, softplus, pooling, the update) is not counted:
it is not matrix work and the peak is the matrix unit's.
"""

HAND_FORWARD_MACS = 1176854048
HAND_TRAIN_OPS = 6834681984


def conv_layers(config):
    """[{"input": (h, w, c), "kernel": (ky, kx, ci, co), "output":
    (h, w, c), "macs": per image, "first": bool}] for the conv layers."""
    h, w, c = config["input_shape"]
    out, first = [], True
    for layer in config["layers"]:
        kind = layer["type"]
        if kind == "conv":
            s = layer["stride"]
            oh, ow, co = -(-h // s), -(-w // s), layer["kernels"]
            out.append({
                "input": (h, w, c),
                "kernel": (layer["ky"], layer["kx"], c, co),
                "output": (oh, ow, co),
                "macs": oh * ow * co * layer["ky"] * layer["kx"] * c,
                "first": first})
            h, w, c = oh, ow, co
        elif kind == "max_pool":
            k, s = layer["k"], layer["stride"]
            h, w = (h - k) // s + 1, (w - k) // s + 1
        else:
            h, w, c = 1, 1, layer["units"]
        first = False
    return out


def dense_layers(config):
    """[(fan_in, units)] for the dense layers."""
    h, w, c = config["input_shape"]
    out = []
    for layer in config["layers"]:
        kind = layer["type"]
        if kind == "conv":
            s = layer["stride"]
            h, w, c = -(-h // s), -(-w // s), layer["kernels"]
        elif kind == "max_pool":
            k, s = layer["k"], layer["stride"]
            h, w = (h - k) // s + 1, (w - k) // s + 1
        else:
            out.append((h * w * c, layer["units"]))
            h, w, c = 1, 1, layer["units"]
    return out


def forward_macs(config):
    return (sum(c["macs"] for c in conv_layers(config))
            + sum(a * b for a, b in dense_layers(config)))


def train_ops_per_image(config):
    """Operations of forward + weight gradients + input gradients for
    one image; the first layer needs no input gradient."""
    convs = conv_layers(config)
    denses = dense_layers(config)
    macs = 3 * forward_macs(config)
    if config["layers"][0]["type"] == "conv":
        macs -= convs[0]["macs"]
    elif denses:
        macs -= denses[0][0] * denses[0][1]
    return 2 * macs


def eval_ops_per_image(config):
    return 2 * forward_macs(config)


def conv_role(config, batch, shapes):
    """Which convolution an op is, from the dims in its text: returns
    ``(layer index, role)`` with role ``forward`` / ``input_grad`` /
    ``weight_grad`` when the op's output and operands hold the layer's
    three tensors (input and output activations at ``batch`` rows and
    the kernel), else None. ``shapes`` is ``trace.parse_op(...)
    ["shapes"]``, output first."""
    dims = [d for _, d in shapes]
    if not dims:
        return None
    for index, conv in enumerate(conv_layers(config)):
        x = (batch,) + conv["input"]
        y = (batch,) + conv["output"]
        k = conv["kernel"]
        if not all(any(sorted(d) == sorted(want) for d in dims)
                   for want in (x, y, k)):
            continue
        head = sorted(dims[0])
        if head == sorted(k):
            return index, "weight_grad"
        if head == sorted(y) and sorted(x) != sorted(y):
            return index, "forward"
        if head == sorted(x) and sorted(x) != sorted(y):
            return index, "input_grad"
        # input and output of the same size (conv4): the operand that
        # is not the kernel tells nothing; call it by position
        return index, "forward_or_input_grad"
    return None


def conv_ops_and_bytes(config, batch, index, shapes):
    """(operations, bytes) one execution of conv ``index`` needs at
    ``batch`` rows: 2 x multiply-adds, and each of the three tensors
    moved once at the width the op's text gives it."""
    conv = conv_layers(config)[index]
    width = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1,
             "f8e4m3fn": 1, "f8e5m2": 1}
    wants = [(batch,) + conv["input"], (batch,) + conv["output"],
             conv["kernel"]]
    total = 0
    for want in wants:
        sizes = [width.get(dtype, 4) for dtype, d in shapes
                 if sorted(d) == sorted(want)]
        elements = 1
        for n in want:
            elements *= n
        total += elements * (min(sizes) if sizes else 4)
    return 2 * batch * conv["macs"], total
