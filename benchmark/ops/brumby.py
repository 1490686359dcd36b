"""Operations and bytes the Brumby-14B-Base serving programs need, from
shapes.

A multiply-add is two operations; weights are at the width the
configuration serves in (bfloat16, 2 bytes), the retention state in
float32 (4 bytes). Operations come from the parameters a token uses
(its layer's projections, the gate, the SwiGLU, the head) and from the
retention itself. Bytes of a decode step: every weight once, and each
live slot's state read once and written once. The state is counted at
the symmetric square's own size, ``D = d(d+1)/2`` distinct products a
K/V head, whatever layout the program holds (``ops/retention.py`` holds
``(d/2 + 1) d``, 0.8% more): the same work whatever implements it.

Hand counts at ``benchmark/configs/brumby-14b-base.json`` (hidden
5120, 40 query heads over 8 K/V heads of 128, SwiGLU of 17408, 8
layers, vocabulary 151,936, head untied):

  wq, wout: 5120 * 5120                        = 26,214,400 each
  wk, wv: 5120 * 1024                          =  5,242,880 each
  the gate: 5120 * 8 (+ 8 of the bias)         =     40,960 (40,968)
  the SwiGLU: 3 * 5120 * 17408                 = 267,386,880
  a layer's matrices                           = 330,342,400
  a layer's parameters (+ 2*5120 + 2*128 of the norms, + the bias)
                                               = 330,352,904
  the head (and the embedding, each)           = 777,912,320
  matrices a token uses: 8 * 330,342,400 + 777,912,320
                                               = 3,420,651,520
  parameters held: 8 * 330,352,904 + 2 * 777,912,320 + 5,120
                                               = 4,198,652,992
  D = 128 * 129 / 2                            = 8,256
  the state of a slot a layer: 8 * 8,256 * (128 + 1) * 4 B
                                               = 34,080,768 B
  the state of a slot, 8 layers                = 272,646,144 B
  a decode step of 16 live slots: weights 2 * 3,420,651,520
        = 6,841,303,040 B, + 2 * 16 * 272,646,144 = 8,724,676,608 B
                                               = 15,565,979,648 B
        (19.0 ms at 819 GB/s, 56% of it the states)
"""

HAND_LAYER_MATRICES = 330342400
HAND_LAYER_PARAMETERS = 330352904
HAND_HEAD = 777912320
HAND_PER_TOKEN = 3420651520
HAND_PARAMETERS = 4198652992
HAND_FEATURES = 8256
HAND_STATE_BYTES_PER_SLOT_LAYER = 34080768
HAND_STATE_BYTES_PER_SLOT = 272646144
HAND_STEP_BYTES_16 = 15565979648
WIDTH = 2
STATE_WIDTH = 4


def layer_matrices(config):
    e, d = config["hidden_size"], config["head_dim"]
    q = config["n_head"] * d
    kv = config["num_key_value_heads"] * d
    return (2 * e * q + 2 * e * kv + e * config["num_key_value_heads"]
            + 3 * e * config["intermediate_size"])


def layer_parameters(config):
    """A layer's matrices, its norms (two of the hidden size, two of a
    head) and the gate's bias."""
    return layer_matrices(config) + 2 * config["hidden_size"] \
        + 2 * config["head_dim"] + config["num_key_value_heads"]


def head_parameters(config):
    return config["hidden_size"] * config["vocab_size"]


def per_token(config):
    """Matrix entries a token multiplies: every layer's and the head."""
    return config["num_hidden_layers"] * layer_matrices(config) \
        + head_parameters(config)


def parameters(config):
    """Everything held: the layers, embedding and head, final norm."""
    return config["num_hidden_layers"] * layer_parameters(config) \
        + 2 * head_parameters(config) + config["hidden_size"]


def features(config):
    """Distinct products of a head's ``d`` values: the symmetric
    square, what a state of degree 2 needs a K/V head."""
    d = config["head_dim"]
    return d * (d + 1) // 2


def state_bytes_per_slot_layer(config):
    """``S`` (D x d) and ``z`` (D) a K/V head, float32."""
    return config["num_key_value_heads"] * features(config) \
        * (config["head_dim"] + 1) * STATE_WIDTH


def state_bytes_per_slot(config):
    return config["num_hidden_layers"] * state_bytes_per_slot_layer(config)


def retention_state(config, live):
    """(operations, bytes) of the state's update and query of ONE
    decode step over ``live`` slots, all layers: each entry of ``S``
    and ``z`` decayed, added to (a product and a sum) and multiplied
    into each of the group's queries; each live slot's state read
    once and written once."""
    d, wide = config["head_dim"], features(config)
    heads, groups = config["n_head"], config["num_key_value_heads"]
    entries = wide * (d + 1)
    ops = config["num_hidden_layers"] * live * (
        3 * groups * entries + 2 * heads * entries)
    return ops, 2 * live * state_bytes_per_slot(config)


def decode_step(config, lengths):
    """(operations, bytes) of ONE decode step over slots whose cached
    lengths are ``lengths`` (one new token each; the lengths change
    nothing: the state does not grow): the matrices a token uses once
    per token and the state's update and query; bytes are every weight
    once and each live slot's state read and written."""
    live = len(lengths)
    state_ops, state_bytes = retention_state(config, live)
    return (2 * per_token(config) * live + state_ops,
            per_token(config) * WIDTH + state_bytes)


def prefill(config, lengths):
    """Operations to prefill prompts of ``lengths``: the layers'
    matrices for every prompt token, the attention form over the
    causal half of the square (a score over ``d`` and a sum over ``d``
    a pair a query head), the state a row leaves (``phi(k) v`` over
    the row's positions, a K/V head), and the head once a prompt."""
    d, layers = config["head_dim"], config["num_hidden_layers"]
    per_pair = layers * config["n_head"] * 4 * d
    per_token_state = layers * config["num_key_value_heads"] \
        * 2 * features(config) * (d + 1)
    blocks = 2 * layers * layer_matrices(config)
    head = 2 * head_parameters(config)
    ops = 0
    for n in lengths:
        ops += (blocks + per_token_state) * n \
            + per_pair * n * (n + 1) // 2 + head
    return ops
