"""Operations and bytes the Command A+ serving programs need, from
shapes.

A multiply-add is two operations; bytes are at the width the
configuration serves in (bfloat16, 2 bytes). Operations come from the
parameters a token uses: its layer's attention, the router, the four
shared experts, its routed experts that this chip holds (8 of 128
chosen, 16 held: one a token a layer on average), the head (the
embedding table's slice, tied). Bytes of a decode step: the weights
outside the routed experts once, the held experts that the step's
tokens touch once, and the K and V rows the live slots attend: a
window layer's last ``min(length, window)`` positions, the global
layer's whole sequence.

Hand counts at ``benchmark/configs/command-a-plus-05-2026.json`` (hidden
4096, 128 query heads over 8 K/V heads of 128, 16 of 128 experts held
of width 4096, top-8, 4 shared experts, 4 layers: 3 window + 1 global,
window 4096; vocabulary slice 32,768, head tied):

  W_q, W_o: 4096 * 16384                          = 67,108,864 each
  W_k, W_v: 4096 * 1024                           =  4,194,304 each
  attention                                       = 142,606,336
  router: 4096 * 128                              =     524,288
  one expert: 3 * 4096 * 4096                     =  50,331,648
  the four shared                                 = 201,326,592
  a layer outside the routed experts              = 344,457,216
  a layer as held (+ 16 experts, + the norm)      = 1,149,767,680
  matrices a token uses, a layer: 344,457,216 + 8 * 16 / 128 experts
                                                  = 394,788,864
        (789.6 MFLOP a prompt token a layer)
  the head: 32,768 * 4096                         = 134,217,728
  parameters held: 4 * 1,149,767,680 + 134,217,728 + 4096
                                                  = 4,733,292,544
  K and V of a cached position a layer: 2 * 8 * 128 * 2 B = 4,096 B
  a slot's rings: 3 * 4096 * 4,096 B              = 50,331,648 B
  experts a step of 48 tokens touches in a layer, choices uniform:
        16 * (1 - (1 - 8/128)**48)                = 15.30
"""

HAND_ATTENTION = 142606336
HAND_EXPERT = 50331648
HAND_OUTSIDE_LAYER = 344457216
HAND_LAYER_HELD = 1149767680
HAND_PER_TOKEN_LAYER = 394788864
HAND_PARAMETERS = 4733292544
HAND_KV_BYTES = 4096
HAND_RING_BYTES = 50331648
WIDTH = 2


def attention_parameters(config):
    e, d = config["hidden_size"], config["head_dim"]
    return 2 * e * config["n_head"] * d \
        + 2 * e * config["num_key_value_heads"] * d


def expert_parameters(config):
    return 3 * config["hidden_size"] * config["intermediate_size"]


def outside_experts_layer(config):
    """A layer's matrices outside the routed experts: attention, the
    router, the shared experts."""
    return attention_parameters(config) \
        + config["hidden_size"] * config["routed_experts"] \
        + config["num_shared_experts"] * expert_parameters(config)


def layer_parameters(config):
    """A layer as held: its matrices, the held experts, its norm."""
    return outside_experts_layer(config) \
        + config["num_experts"] * expert_parameters(config) \
        + config["hidden_size"]


def head_parameters(config):
    return config["hidden_size"] * config["vocab_size"]


def parameters(config):
    return config["num_hidden_layers"] * layer_parameters(config) \
        + head_parameters(config) + config["hidden_size"]


def held_per_token(config):
    """Routed experts a token multiplies on this chip, a layer: its
    ``top_k`` choices of ``routed_experts``, the held share of them."""
    return config["num_experts_per_tok"] * config["num_experts"] \
        / config["routed_experts"]


def per_token(config):
    """Matrix entries a token multiplies: every layer's and the head."""
    return config["num_hidden_layers"] * (
        outside_experts_layer(config)
        + held_per_token(config) * expert_parameters(config)) \
        + head_parameters(config)


def kinds(config):
    """(window layers, global layers)."""
    types = config["layer_types"]
    return types.count("sliding_attention"), types.count("full_attention")


def kv_bytes(config):
    """K and V of one cached position of one layer."""
    return 2 * WIDTH * config["num_key_value_heads"] * config["head_dim"]


def ring_bytes(config):
    """A slot's rings: the window layers' K and V of a window each."""
    return kinds(config)[0] * config["sliding_window"] * kv_bytes(config)


def expected_touched(config, tokens):
    """Held experts of a layer that ``tokens`` tokens touch, if every
    choice were uniform over all the routed experts."""
    chosen = config["num_experts_per_tok"] / config["routed_experts"]
    return config["num_experts"] * (1.0 - (1.0 - chosen) ** tokens)


def attended(config, n):
    """Positions a token at position ``n`` (``n`` cached before it)
    attends, over the layers: each window layer the last ``min(n + 1,
    window)``, the global layer all ``n + 1``."""
    window, glob = kinds(config)
    return window * min(n + 1, config["sliding_window"]) + glob * (n + 1)


def attend_ops_per_position(config):
    """A query token's operations against one attended position of one
    layer: 128 heads' scores over 128 and sums over 128."""
    return 2 * config["n_head"] * 2 * config["head_dim"]


def window_attend(config, lengths):
    """(operations, bytes) of ONE decode step's attend in the window
    and global layers over slots whose cached lengths are ``lengths``:
    every head's scores and sums over the positions each slot attends
    (:func:`attended`), each of their K and V rows read once."""
    positions = sum(attended(config, n) for n in lengths)
    return (attend_ops_per_position(config) * positions,
            kv_bytes(config) * positions)


def decode_step(config, lengths):
    """(operations, bytes) of ONE decode step over slots whose cached
    lengths are ``lengths`` (one new token each): the matrices a token
    uses once per token and the attend; bytes are the weights outside
    the routed experts once, the held experts touched (at uniform
    choices) once, and the K and V rows attended."""
    tokens = len(lengths)
    attend_ops, attend_bytes = window_attend(config, lengths)
    nbytes = ((config["num_hidden_layers"] * (
        outside_experts_layer(config)
        + expected_touched(config, tokens) * expert_parameters(config))
        + head_parameters(config)) * WIDTH + attend_bytes)
    return 2 * per_token(config) * tokens + attend_ops, nbytes


def prompt_pairs(config, n):
    """(query, key) pairs the layers attend over a prompt of ``n``:
    a window layer ``sum_t min(t + 1, window)``, the global layer the
    causal half of the square."""
    window, glob = kinds(config)
    w = min(n, config["sliding_window"])
    in_window = w * (w + 1) // 2 + (n - w) * config["sliding_window"]
    return window * in_window + glob * n * (n + 1) // 2


def prompt_attend(config, n):
    """Operations of the prompt attention over a row of ``n``
    positions, every layer."""
    return attend_ops_per_position(config) * prompt_pairs(config, n)


def prefill(config, lengths):
    """Operations to prefill prompts of ``lengths``: the matrices a
    token uses for every prompt token (the head once a prompt) and the
    prompt attention."""
    blocks = 2 * (per_token(config) - head_parameters(config))
    head = 2 * head_parameters(config)
    return sum(blocks * n + prompt_attend(config, n) + head
               for n in lengths)
