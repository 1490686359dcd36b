"""Operations and bytes the JoyAI-LLM-Flash serving programs need,
from shapes.

A multiply-add is two operations; bytes are at the width the
configuration serves in (bfloat16, 2 bytes). Operations come from the
parameters a token uses: attention, the dense feed-forward or the
router + 8 routed + 1 shared experts, the head. Bytes of a decode
step: the weights outside the routed experts once, the routed experts
that a token chose once, and the cached rows of the live positions.

Hand counts at ``benchmark/configs/joyai-llm-flash.json`` (hidden
2048, 32 heads, q rank 1536, kv rank 512, nope 128, rope 64, v 128,
dense width 7168, 256 experts of width 768 top-8 + 1 shared, 1 dense +
4 expert layers, vocabulary 129,280):

  attention of a layer: 2048*1536 + 1536*32*192 + 2048*576
        + 512*32*256 + 4096*2048              = 26,345,472
  one expert: 3 * 2048 * 768                  =  4,718,592
  the dense layer: 26,345,472 + 3*2048*7168   = 70,385,664
  an expert layer outside its routed experts:
        26,345,472 + 2048*256 + 4,718,592     = 31,588,352
  matrices outside the routed experts:
        70,385,664 + 4*31,588,352 + 2048*129,280
                                              = 461,504,512
  matrices a token uses: 461,504,512 + 4*8*4,718,592
                                              = 612,499,456
  a cached position: 5 layers x 576 x 2 B     = 5,760 B
  experts a step of 32 tokens touches in a layer, choices uniform:
        256 * (1 - (1 - 8/256)**32)           = 163.3
  (the embedding table is gathered, a row a slot, and not counted)
"""

HAND_ATTENTION_PARAMETERS = 26345472
HAND_EXPERT_PARAMETERS = 4718592
HAND_OUTSIDE_EXPERTS = 461504512
HAND_PER_TOKEN = 612499456
HAND_ROW_BYTES_PER_POSITION = 5760
WIDTH = 2


def attention_parameters(config):
    e, heads = config["hidden_size"], config["n_head"]
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v_dim = config["v_head_dim"]
    return (e * q_rank + q_rank * heads * (nope + rope)
            + e * (kv_rank + rope) + kv_rank * heads * (nope + v_dim)
            + heads * v_dim * e)


def expert_parameters(config):
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layers(config):
    """(dense layers, expert layers)."""
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def block_parameters_outside_experts(config):
    e = config["hidden_size"]
    dense, sparse = layers(config)
    return (dense * (attention_parameters(config)
                     + 3 * e * config["intermediate_size"])
            + sparse * (attention_parameters(config)
                        + e * config["n_routed_experts"]
                        + config["n_shared_experts"]
                        * expert_parameters(config)))


def outside_experts(config):
    return block_parameters_outside_experts(config) \
        + config["hidden_size"] * config["vocab_size"]


def routed_per_token(config):
    return layers(config)[1] * config["num_experts_per_tok"] \
        * expert_parameters(config)


def row_bytes_per_position(config):
    return config["num_hidden_layers"] * WIDTH \
        * (config["kv_lora_rank"] + config["qk_rope_head_dim"])


def expected_touched(config, tokens):
    """Experts of a layer that ``tokens`` tokens touch, if every
    choice were uniform."""
    experts = config["n_routed_experts"]
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
    """A query's operations against one cached position, all layers
    (absorbed: the scores over the whole row, the sum over ``c``)."""
    return 2 * config["num_hidden_layers"] * config["n_head"] * (
        2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])


def decode_step(config, lengths):
    """(operations, bytes) of ONE decode step over slots whose cached
    lengths are ``lengths`` (one new token each): the matrices a token
    uses once per token, attention against each slot's own live
    positions; bytes are the weights outside the routed experts once,
    the experts touched (at uniform choices: the program's own count
    is the expert roofline's) and the rows of the live positions."""
    tokens = len(lengths)
    live = sum(n + 1 for n in lengths)
    ops = (2 * (outside_experts(config) + routed_per_token(config))
           * tokens + attend_ops_per_position(config) * live)
    nbytes = ((outside_experts(config)
               + layers(config)[1] * expert_parameters(config)
               * expected_touched(config, tokens)) * WIDTH
              + row_bytes_per_position(config) * live)
    return ops, nbytes


def prefill(config, lengths):
    """Operations to prefill prompts of ``lengths``: the block
    matrices a token uses for every prompt token, causal expanded
    attention (half the square, scores over 192 and the sum over 128 a
    head), and the head once per prompt."""
    heads = config["n_head"]
    per_pair = 2 * config["num_hidden_layers"] * heads * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])
    head = 2 * config["hidden_size"] * config["vocab_size"]
    per_token = 2 * (block_parameters_outside_experts(config)
                     + routed_per_token(config))
    ops = 0
    for n in lengths:
        ops += per_token * n + per_pair * n * (n + 1) // 2 + head
    return ops
