"""Operations and bytes the Solar Open 2 serving programs need, from
shapes.

A multiply-add is two operations; weights are at the width the
configuration serves in (bfloat16, 2 bytes), the delta rule's state in
float32 (4 bytes). Operations come from the parameters a token uses
(its layer's mixer, the router, the shared expert, its routed experts
that this chip holds: 8 of 320 chosen, 40 held, one a token a layer on
average; the head), from the GQA layer's attention over the actual
lengths and from the delta rule's recurrence (a head's state decayed,
read with k, corrected and read with q: 7 d^2 a token a head, whatever
form computes it). Bytes of a decode step: the weights outside the
routed experts once, the held experts that the step's tokens touch
once, the K and V rows the live slots attend in the GQA layer, and each
live slot's delta-rule state read once and written once with its q, k,
v, decays and write strengths, and its convolutions' tails.

Hand counts at ``benchmark/configs/solar-open2-250b.json`` (hidden
4096, 64 heads of 128, 8 K/V heads in the GQA layer, KDA low rank 128,
40 of 320 experts held of width 1280, top-8, 1 shared; 4 layers: 1 GQA
+ 3 KDA; vocabulary slice 24,576, head untied):

  one expert: 3 * 4096 * 1280                     =  15,728,640
  the 40 held                                     = 629,145,600
  KDA mixer: w_qkv 3 * 4096 * 8192 = 100,663,296, W_o 33,554,432,
      taps 4 * 24,576 = 98,304, W_fa + W_fb 2 * 524,288 + ... = 1,572,864,
      dt_bias + A_log 8,192 + 64, W_beta 4096 * 64 = 262,144,
      W_ga + W_gb 1,572,864, the head norm 128     = 137,732,288
  GQA mixer: W_q, W_gate, W_o 33,554,432 each, W_k, W_v 4,194,304 each
                                                  = 109,051,904
  outside the mixer: router 4096 * 320 = 1,310,720, its bias 320, the
      shared expert 15,728,640, two norms 8,192   =  17,047,872
  a KDA layer as held                             = 783,925,760
  the GQA layer as held                           = 755,245,376
  a period (1 GQA + 3 KDA)                        = 3,107,022,656
  embedding + head 2 * 24,576 * 4096 + the norm   = 201,330,688
  parameters held                                 = 3,308,353,344 (6.62 GB)
  matrices a token multiplies: KDA 137,625,600 a layer (the mixer less
      its taps, biases and norm), GQA 109,051,904, and a layer's router,
      shared expert and one held expert 32,768,000; the head 100,663,296
  the delta rule's state a slot: 3 * 64 * 128 * 128 * 4 B
                                                  =  12,582,912 B
  the convolutions' tails a slot: 3 * 3 * 24,576 * 2 B = 442,368 B
  K and V of a cached position (the GQA layer): 2 * 8 * 128 * 2 B = 4,096 B
  experts a step of 128 tokens touches in a layer, choices uniform:
        40 * (1 - (1 - 8/320)**128)               = 38.4
"""

HAND_EXPERT = 15728640
HAND_KDA_MIXER = 137732288
HAND_GQA_MIXER = 109051904
HAND_OUTSIDE_MIXER = 17047872
HAND_KDA_LAYER = 783925760
HAND_GQA_LAYER = 755245376
HAND_PERIOD = 3107022656
HAND_EMBED_HEAD = 201330688
HAND_PARAMETERS = 3308353344
HAND_STATE_BYTES_PER_SLOT = 12582912
HAND_CONV_BYTES_PER_SLOT = 442368
HAND_KV_BYTES = 4096
WIDTH = 2
STATE_WIDTH = 4


def kinds(config):
    """(GQA layers, KDA layers)."""
    glob = len(config["gqa_layers"])
    return glob, config["num_hidden_layers"] - glob


def projected(config):
    """H·D: the width of the heads' q, k, v and the state's outputs."""
    return config["n_head"] * config["head_dim"]


def kda_matrices(config):
    """A KDA mixer's matrices: the three streams, the low-rank decay and
    output gate (rank ``head_dim``), the write strength, the output."""
    e, hd, r = config["hidden_size"], projected(config), config["head_dim"]
    return 3 * e * hd + hd * e + 2 * (e * r + r * hd) \
        + e * config["n_head"]


def kda_mixer(config):
    """The matrices, the taps, ``dt_bias``, ``A_log`` and the head norm."""
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    hd = projected(config)
    return kda_matrices(config) + taps * 3 * hd + hd + config["n_head"] \
        + config["head_dim"]


def gqa_mixer(config):
    e, d = config["hidden_size"], config["head_dim"]
    return 3 * e * projected(config) \
        + 2 * e * config["num_key_value_heads"] * d


def expert_parameters(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def outside_mixer(config):
    """A layer's router and its bias, the shared experts, two norms."""
    e, routed = config["hidden_size"], config["routed_experts"]
    return e * routed + routed \
        + config["n_shared_experts"] * expert_parameters(config) + 2 * e


def held_experts(config):
    return config["n_routed_experts"] * expert_parameters(config)


def head_parameters(config):
    return config["hidden_size"] * config["vocab_size"]


def parameters(config):
    """Everything held: the layers, embedding and head, final norm."""
    glob, kda = kinds(config)
    layer = outside_mixer(config) + held_experts(config)
    return glob * (gqa_mixer(config) + layer) \
        + kda * (kda_mixer(config) + layer) \
        + 2 * head_parameters(config) + config["hidden_size"]


def held_per_token(config):
    """Routed experts a token multiplies on this chip, a layer: its
    ``top_k`` choices of ``routed_experts``, the held share of them."""
    return config["num_experts_per_tok"] * config["n_routed_experts"] \
        / config["routed_experts"]


def ffn_per_token(config):
    """A layer's feed-forward matrices a token multiplies: the router,
    the shared experts and its held share of the routed."""
    return config["hidden_size"] * config["routed_experts"] \
        + (config["n_shared_experts"] + held_per_token(config)) \
        * expert_parameters(config)


def per_token(config):
    """Matrix entries a token multiplies: every layer's and the head."""
    glob, kda = kinds(config)
    return glob * gqa_mixer(config) + kda * kda_matrices(config) \
        + (glob + kda) * ffn_per_token(config) + head_parameters(config)


def recurrence_ops(config):
    """A token's operations in the delta rule, all KDA layers: each
    entry of a head's state decayed (1), read with k (2), corrected (2)
    and read with q (2)."""
    return kinds(config)[1] * 7 * config["n_head"] \
        * config["head_dim"] ** 2


def state_bytes_per_slot(config):
    d = config["head_dim"]
    return kinds(config)[1] * config["n_head"] * d * d * STATE_WIDTH


def conv_bytes_per_slot(config):
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    return kinds(config)[1] * (taps - 1) * 3 * projected(config) * WIDTH


def kda_state(config, live):
    """(operations, bytes) of the delta rule's state in ONE decode step
    over ``live`` slots, all KDA layers: the recurrence's operations;
    each live slot's state read once and written once, and its q, k, v
    and decays (float32, a channel) and write strengths (a head) read
    once."""
    rows = kinds(config)[1] * (4 * projected(config) + config["n_head"]) \
        * STATE_WIDTH
    return (live * recurrence_ops(config),
            live * (2 * state_bytes_per_slot(config) + rows))


def kv_bytes(config):
    """K and V of one cached position of the GQA layer."""
    return 2 * WIDTH * config["num_key_value_heads"] * config["head_dim"]


def attend_ops_per_position(config):
    """A query token's operations against one attended position of the
    GQA layer: 64 heads' scores over 128 and sums over 128."""
    return 2 * config["n_head"] * 2 * config["head_dim"]


def expected_touched(config, tokens):
    """Held experts of a layer that ``tokens`` tokens touch, if every
    choice were uniform over all the routed experts."""
    chosen = config["num_experts_per_tok"] / config["routed_experts"]
    return config["n_routed_experts"] * (1.0 - (1.0 - chosen) ** tokens)


def decode_step(config, lengths):
    """(operations, bytes) of ONE decode step over slots whose cached
    lengths are ``lengths`` (one new token each): the matrices a token
    uses once per token, the GQA layer's attend over ``n + 1`` positions
    and the delta rule's state; bytes are the weights outside the routed
    experts once, the held experts touched (at uniform choices) once,
    the K and V rows attended, the states and the convolutions' tails
    read and written."""
    tokens = len(lengths)
    glob, kda = kinds(config)
    positions = glob * sum(n + 1 for n in lengths)
    state_ops, state_bytes = kda_state(config, tokens)
    weights = glob * gqa_mixer(config) + kda * kda_mixer(config) \
        + (glob + kda) * (outside_mixer(config) + expected_touched(
            config, tokens) * expert_parameters(config)) \
        + head_parameters(config)
    ops = 2 * per_token(config) * tokens \
        + attend_ops_per_position(config) * positions + state_ops
    return ops, (weights * WIDTH + kv_bytes(config) * positions
                 + state_bytes + 2 * tokens * conv_bytes_per_slot(config))


#: the least prompt length whose one row the splash kernel always takes
#: at 64 heads (``ops/attention.prompt_path``: 64 x 1,024^2 x 4 B of
#: scores pass its 64 MiB; one row of 512 does not)
KERNEL_BUCKET = 1024


def prompt_attend(config, n):
    """Operations of the splash kernel's prompt attention over a row of
    ``n`` positions (the GQA layer's causal half of the square), or 0
    where one row of ``n`` is XLA's (``KERNEL_BUCKET``)."""
    if n < KERNEL_BUCKET:
        return 0
    return kinds(config)[0] * attend_ops_per_position(config) \
        * n * (n + 1) // 2


def prefill(config, lengths):
    """Operations to prefill prompts of ``lengths``: the matrices a
    token uses for every prompt token (the head once a prompt), the GQA
    layer's causal attention and the delta rule's recurrence."""
    glob = kinds(config)[0]
    blocks = 2 * (per_token(config) - head_parameters(config)) \
        + recurrence_ops(config)
    head = 2 * head_parameters(config)
    return sum(blocks * n + glob * attend_ops_per_position(config)
               * n * (n + 1) // 2 + head for n in lengths)
