"""Bytes and operations of a MiMo-V2-Flash share, from the configuration
file's shapes: what the algorithm needs, not what a program happens to
move or compute, so that a share of the roofline cannot pass 100 %.
Parameters and cached keys and values are counted in the configuration's
dtype (2 bytes), the sinks in float32; the per-layer lists are read up to
`num_hidden_layers`. A layer is full (`hybrid_layer_pattern` 0:
`num_attention_heads` over `num_key_value_heads`) or sliding (1: the
`swa_` counts); keys are `head_dim` deep, values `v_head_dim`; layer i is
dense where `moe_layer_freq[i]` is 0; there is no shared expert.
tests/test_mimo_v2.py and benchmark/tests/test_ref_mimo_v2.py hold the
counts to the built net's leaves and to the pool's allocated bytes."""


def layer_list(c):
    """[(sliding?, dense?)] of the layers that are run."""
    n = c["num_hidden_layers"]
    return [(bool(c["hybrid_layer_pattern"][i]),
             not c["moe_layer_freq"][i]) for i in range(n)]


def heads(c, sliding):
    """(query heads, key-value heads) of a layer of that kind."""
    return (c["swa_num_attention_heads"], c["swa_num_key_value_heads"]) \
        if sliding else (c["num_attention_heads"], c["num_key_value_heads"])


def attention_params(c, sliding):
    """q | k | v and the output projection (the sinks, a float a head,
    are left out with the norms)."""
    H, d, dv = c["hidden_size"], c["head_dim"], c["v_head_dim"]
    n, kv = heads(c, sliding)
    return H * ((n + kv) * d + kv * dv) + n * dv * H


def dense_ffn_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c):
    """One routed expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["share"]["router_width"]


def layer_params(c, sliding, dense, experts):
    """A layer with `experts` routed experts read (all held:
    `n_routed_experts`, the file's held count; in a step: those that got
    a pair); norms and sinks left out."""
    ffn = dense_ffn_params(c) if dense else (
        router_params(c) + experts * expert_params(c))
    return attention_params(c, sliding) + ffn


def held_params(c):
    """Every matrix of the share (norms, sinks and the selection bias left
    out, 0.06 M), embedding and untied head included."""
    return (sum(layer_params(c, sliding, dense, c["n_routed_experts"])
                for sliding, dense in layer_list(c))
            + 2 * c["vocab_size"] * c["hidden_size"])


def token_bytes(c, sliding, itemsize=2):
    """Keys and values of one token in one layer of that kind."""
    return heads(c, sliding)[1] * (c["head_dim"] + c["v_head_dim"]) \
        * itemsize


def layers_of(c, sliding):
    return sum(s == sliding for s, _ in layer_list(c))


def paged_bytes_per_token(c, itemsize=2):
    """What one token holds in the pool: the full layers' keys and
    values."""
    return layers_of(c, False) * token_bytes(c, False, itemsize)


def ring_bytes_per_slot(c, itemsize=2):
    """What one decode slot holds whatever its stream's length: a ring of
    `sliding_window` tokens in every sliding layer."""
    return layers_of(c, True) * c["sliding_window"] \
        * token_bytes(c, True, itemsize)


def decode_step_bytes(c, experts_touched, full_tokens_read,
                      window_tokens_read, rows, itemsize=2):
    """One decode step: every layer's attention and router, the
    `experts_touched` (mean per expert layer-step) routed experts that got
    a pair, the dense layer's FFN, the head, the `rows` embedding rows
    looked up, and the cached keys and values attended to:
    `full_tokens_read` (summed over slots and full layers, each slot's
    whole stream) and `window_tokens_read` (over slots and sliding layers,
    at most the window each), as the program counts them, each kind at
    its own bytes a token."""
    params = (sum(layer_params(c, sliding, dense, experts_touched)
                  for sliding, dense in layer_list(c))
              + c["vocab_size"] * c["hidden_size"]
              + rows * c["hidden_size"])
    return params * itemsize \
        + full_tokens_read * token_bytes(c, False, itemsize) \
        + window_tokens_read * token_bytes(c, True, itemsize)


def sink_gqa_call_cost(c, sliding, slots, tokens_read, itemsize=2):
    """(operations, bytes) of one call of the grouped-query paged kernel
    in its sink / two-width form, one layer of that kind, `slots` slots of
    one token each over `tokens_read` cached tokens in all (a full layer:
    the streams' lengths; a sliding layer: at most the window a slot): a
    product of 2 `head_dim` and one of 2 `v_head_dim` operations a query
    head a cached token; each token's keys and values read ONCE for their
    whole group, the queries in (`head_dim` a head), the outputs out
    (`v_head_dim`), and a sliding layer's sinks (float32, once a call)."""
    n, _ = heads(c, sliding)
    d, dv = c["head_dim"], c["v_head_dim"]
    has_sinks = c["add_swa_attention_sink_bias"] if sliding \
        else c["add_full_attention_sink_bias"]
    ops = 2 * n * (d + dv) * tokens_read
    moved = tokens_read * token_bytes(c, sliding, itemsize) \
        + slots * n * (d + dv) * itemsize + (n * 4 if has_sinks else 0)
    return ops, moved
