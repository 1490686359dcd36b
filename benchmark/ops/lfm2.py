"""Operations and bytes the LFM2-8B-A1B serving programs need, from
shapes.

A multiply-add is two operations; bytes are at the width the
configuration serves in (bfloat16, 2 bytes). Operations come from the
parameters a token uses: its layer's operator (a gated short
convolution or grouped-query attention), the dense feed-forward or
the router + 4 routed experts, the head (the embedding table, tied).
Bytes of a decode step: the weights outside the routed experts once,
the routed experts that the step's tokens chose once, the K and V rows
of the live positions in the attention layers, and the convolution's
state, read and written once a step.

Hand counts at ``benchmark/configs/lfm2-8b-a1b.json`` (hidden 2048, 32
query heads over 8 K/V heads of 64, a convolution of 3 taps, dense
width 7168, 32 experts of width 1792 top-4 and no shared expert, 13
layers: 10 conv + 3 attention, of which the first (conv) is dense;
vocabulary 65,536, head tied):

  a conv operator: 2048*6144 + 3*2048 + 2048*2048   = 16,783,360
  an attention operator: 2*2048*2048 + 2*2048*512   = 10,485,760
        (+ 2 x 64 of the q and k norms, not matrices)
  one expert: 3 * 2048 * 1792                       = 11,010,048
  the dense feed-forward: 3 * 2048 * 7168           = 44,040,192
  a router: 2048 * 32                               =     65,536
  matrices outside the routed experts:
        10*16,783,360 + 3*10,485,760 + 44,040,192
        + 12*65,536 + 2048*65,536                   = 378,335,232
  matrices a token uses: 378,335,232 + 12*4*11,010,048
                                                    = 906,817,536
  K and V of a cached position: 3 layers x 2 x 512 x 2 B = 6,144 B
        (1,024 B a position a leaf)
  the conv state of a slot: 10 layers x 2 x 2048 x 2 B = 81,920 B
  experts a step of 64 tokens touches in a layer, choices uniform:
        32 * (1 - (1 - 4/32)**64)                   = 31.99
  (the embedding table is gathered, a row a slot, and counted once,
  as the head)
"""

HAND_CONV_PARAMETERS = 16783360
HAND_ATTENTION_PARAMETERS = 10485760
HAND_EXPERT_PARAMETERS = 11010048
HAND_OUTSIDE_EXPERTS = 378335232
HAND_PER_TOKEN = 906817536
HAND_KV_BYTES_PER_POSITION = 6144
HAND_STATE_BYTES_PER_SLOT = 81920
WIDTH = 2


def conv_parameters(config):
    e = config["hidden_size"]
    return e * 3 * e + config["conv_L_cache"] * e + e * e


def attention_parameters(config):
    e, heads = config["hidden_size"], config["n_head"]
    head_dim = e // heads
    return 2 * e * heads * head_dim \
        + 2 * e * config["num_key_value_heads"] * head_dim


def expert_parameters(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def kinds(config):
    """(conv layers, attention layers)."""
    types = config["layer_types"]
    return types.count("conv"), types.count("full_attention")


def layers(config):
    """(dense layers, expert layers)."""
    dense = config["num_dense_layers"]
    return dense, config["num_hidden_layers"] - dense


def block_parameters_outside_experts(config):
    e = config["hidden_size"]
    conv, attention = kinds(config)
    dense, sparse = layers(config)
    return (conv * conv_parameters(config)
            + attention * attention_parameters(config)
            + dense * 3 * e * config["intermediate_size"]
            + sparse * e * config["num_experts"])


def outside_experts(config):
    return block_parameters_outside_experts(config) \
        + config["hidden_size"] * config["vocab_size"]


def routed_per_token(config):
    return layers(config)[1] * config["num_experts_per_tok"] \
        * expert_parameters(config)


def kv_bytes_per_position(config):
    head_dim = config["hidden_size"] // config["n_head"]
    return kinds(config)[1] * 2 * WIDTH \
        * config["num_key_value_heads"] * head_dim


def state_bytes_per_slot(config):
    return kinds(config)[0] * (config["conv_L_cache"] - 1) \
        * config["hidden_size"] * WIDTH


def expected_touched(config, tokens):
    """Experts of a layer that ``tokens`` tokens touch, if every
    choice were uniform."""
    experts = config["num_experts"]
    return experts * (1.0 - (1.0 - config["num_experts_per_tok"]
                             / experts) ** tokens)


def expert_products(config, assignments, touched):
    """(operations, bytes) of the routed experts' three products for
    ``assignments`` (token, expert) pairs over ``touched`` distinct
    experts: each assignment multiplies one expert's matrices, each
    touched expert is read once, and a row of 2048 goes in and comes
    out an assignment."""
    ops = 2 * expert_parameters(config) * assignments
    nbytes = (expert_parameters(config) * touched
              + 2 * config["hidden_size"] * assignments) * WIDTH
    return ops, nbytes


def attend_ops_per_position(config):
    """A query token's operations against one cached position, the
    attention layers: 32 heads' scores over 64 and sums over 64."""
    head_dim = config["hidden_size"] // config["n_head"]
    return 2 * kinds(config)[1] * config["n_head"] * 2 * head_dim


def decode_step(config, lengths):
    """(operations, bytes) of ONE decode step over slots whose cached
    lengths are ``lengths`` (one new token each): the matrices a token
    uses once per token, attention against each slot's own live
    positions; bytes are the weights outside the routed experts once,
    the experts touched (at uniform choices: the program's own count
    is the expert roofline's), the K and V rows of the live positions
    and each slot's conv state read and written."""
    tokens = len(lengths)
    live = sum(n + 1 for n in lengths)
    ops = (2 * (outside_experts(config) + routed_per_token(config))
           * tokens + attend_ops_per_position(config) * live)
    nbytes = ((outside_experts(config)
               + layers(config)[1] * expert_parameters(config)
               * expected_touched(config, tokens)) * WIDTH
              + kv_bytes_per_position(config) * live
              + 2 * state_bytes_per_slot(config) * tokens)
    return ops, nbytes


def prefill(config, lengths):
    """Operations to prefill prompts of ``lengths``: the block
    matrices a token uses for every prompt token, causal attention in
    the attention layers (half the square, scores and sums over 64 a
    head), and the head once per prompt."""
    per_pair = attend_ops_per_position(config)
    head = 2 * config["hidden_size"] * config["vocab_size"]
    per_token = 2 * (block_parameters_outside_experts(config)
                     + routed_per_token(config))
    ops = 0
    for n in lengths:
        ops += per_token * n + per_pair * n * (n + 1) // 2 + head
    return ops
