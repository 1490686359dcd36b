"""Operations and bytes the GPT-2 serving programs need, from shapes.

A multiply-add is two operations; bytes are at the width the
configuration serves in (bfloat16, 2 bytes).

Hand counts at ``benchmark/configs/gpt2-medium.json`` (E 1024, 16
heads of 64, 24 layers, inner 4096, vocabulary 50257):

  K/V a position: 24 layers x 2 x 1024 x 2 B = 98,304 B = 96 KiB
  matrix parameters a block: 1024*3072 + 1024*1024 + 2*1024*4096
                                              = 12,582,912
  matrix parameters: 24 * 12,582,912 + 1024*50257 (head)
                                              = 353,453,056
  weights read by a decode step: 353,453,056 * 2 B = 706,906,112 B
  (the embedding table is gathered, a row a slot, and not counted)
"""

HAND_KV_BYTES_PER_POSITION = 98304
HAND_MATRIX_PARAMETERS = 353453056
WIDTH = 2


def kv_bytes_per_position(config):
    return config["n_layer"] * 2 * config["n_embd"] * WIDTH


def matrix_parameters(config):
    e, hidden = config["n_embd"], config["n_inner"]
    return (config["n_layer"] * (e * 3 * e + e * e + 2 * e * hidden)
            + e * config["vocab_size"])


def block_matrix_parameters(config):
    e, hidden = config["n_embd"], config["n_inner"]
    return config["n_layer"] * (e * 3 * e + e * e + 2 * e * hidden)


def decode_step(config, lengths):
    """(operations, bytes) of ONE decode step over slots whose cached
    lengths are ``lengths`` (one new token each): the matrices once per
    token, attention against each slot's own live positions; bytes are
    the weights once and the K/V of the live positions."""
    e = config["n_embd"]
    tokens = len(lengths)
    live = sum(n + 1 for n in lengths)
    ops = (2 * matrix_parameters(config) * tokens
           + 2 * 2 * config["n_layer"] * e * live)
    nbytes = (matrix_parameters(config) * WIDTH
              + kv_bytes_per_position(config) * live)
    return ops, nbytes


def prefill(config, lengths):
    """Operations to prefill prompts of ``lengths``: the block matrices
    for every prompt token, causal attention (half the square), and
    the head once per prompt."""
    e = config["n_embd"]
    ops = 0
    for n in lengths:
        ops += 2 * block_matrix_parameters(config) * n
        ops += 2 * 2 * config["n_layer"] * e * n * (n + 1) // 2
        ops += 2 * e * config["vocab_size"]
    return ops
