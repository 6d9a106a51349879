"""Bytes and operations of a Laguna share, from the configuration file's
shapes: what the algorithm needs, not what a program happens to move or
compute, so that a share of the roofline cannot pass 100 %. Parameters
and cached keys and values are counted in the configuration's dtype (2
bytes); the per-layer lists are read up to `num_hidden_layers`.
benchmark/tests/test_ref_laguna.py holds the counts to the built net's
leaves and to the pool's allocated bytes."""

FULL, SLIDING = "full_attention", "sliding_attention"


def layer_list(c):
    """[(kind, query heads, dense?)] of the layers that are run."""
    n = c["num_hidden_layers"]
    return [(c["layer_types"][i], c["num_attention_heads_per_layer"][i],
             i in c["mlp_only_layers"]) for i in range(n)]


def attention_params(c, heads):
    """q | k | v, the gate a head and the output projection of a layer
    with `heads` query heads."""
    H, d, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    return H * (heads + 2 * kv) * d + H * heads + heads * d * H


def dense_ffn_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c):
    """One routed expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_expert_params(c):
    return 3 * c["hidden_size"] * c["shared_expert_intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["share"]["router_width"]


def layer_params(c, heads, dense, experts):
    """A layer with `experts` routed experts read (all held:
    `num_experts`; in a step: those that got a pair); norms left out."""
    ffn = dense_ffn_params(c) if dense else (
        router_params(c) + shared_expert_params(c)
        + experts * expert_params(c))
    return attention_params(c, heads) + ffn


def held_params(c):
    """Every matrix of the share (norms and the selection bias left out,
    0.08 M), embedding and untied head included."""
    return (sum(layer_params(c, heads, dense, c["num_experts"])
                for _, heads, dense in layer_list(c))
            + 2 * c["vocab_size"] * c["hidden_size"])


def token_bytes(c, itemsize=2):
    """Keys and values of one token in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def layers_of(c, kind):
    return sum(k == kind for k, _, _ in layer_list(c))


def paged_bytes_per_token(c, itemsize=2):
    """What one token holds in the pool: the full layers' keys and
    values."""
    return layers_of(c, FULL) * token_bytes(c, itemsize)


def ring_bytes_per_slot(c, itemsize=2):
    """What one decode slot holds whatever its stream's length: a ring of
    `sliding_window` tokens in every sliding layer."""
    return layers_of(c, SLIDING) * c["sliding_window"] * token_bytes(
        c, itemsize)


def saved_share(c, live_tokens, pool_blocks_used, block_size, ring_bytes):
    """(bytes held, bytes every layer paged by token would hold) for
    `live_tokens` cached tokens: the full layers' blocks in use and all
    the rings (`ring_bytes`, used or not) against `num_hidden_layers`
    paged layers."""
    every = c["num_hidden_layers"] * live_tokens * token_bytes(c)
    held = pool_blocks_used * block_size * paged_bytes_per_token(c) \
        + ring_bytes
    return held, every


def decode_step_bytes(c, experts_touched, full_tokens_read,
                      window_tokens_read, rows, itemsize=2):
    """One decode step: every layer's attention, router, shared expert
    and the `experts_touched` (mean per expert layer-step) routed experts
    that got a pair, the dense layers' FFN, the head, the `rows`
    embedding rows looked up, and the cached keys and values attended to:
    `full_tokens_read` (summed over slots and full layers, each slot's
    whole stream) and `window_tokens_read` (over slots and sliding layers,
    at most the window each), as the program counts them."""
    params = (sum(layer_params(c, heads, dense, experts_touched)
                  for _, heads, dense in layer_list(c))
              + c["vocab_size"] * c["hidden_size"]
              + rows * c["hidden_size"])
    return params * itemsize + (full_tokens_read + window_tokens_read) \
        * token_bytes(c, itemsize)


def gqa_call_cost(c, heads, slots, tokens_read, itemsize=2):
    """(operations, bytes) of one call of the grouped-query paged kernel,
    one layer with `heads` query heads, `slots` slots of one token each
    over `tokens_read` cached tokens in all (a full layer: the streams'
    lengths; a sliding layer: at most the window a slot): two products of
    2 d operations a query head a cached token; each token's keys and
    values read ONCE for their whole group, the queries in and the
    outputs out."""
    d = c["head_dim"]
    ops = 4 * d * heads * tokens_read
    moved = tokens_read * token_bytes(c, itemsize) \
        + 2 * slots * heads * d * itemsize
    return ops, moved
