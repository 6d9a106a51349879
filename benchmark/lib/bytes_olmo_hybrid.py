"""Bytes and operations of an OLMo-hybrid cut, from the configuration
file's shapes: what the algorithm needs, not what a program happens to
move or compute, so that a share of the roofline cannot pass 100 %.
Parameters are counted in the configuration's dtype (2 bytes); the
recurrent state in `state_dtype` (float32). benchmark/tests/
test_ref_olmo_hybrid.py holds the counts to the built net's leaves and to
the pool's allocated bytes."""

LINEAR = "linear_attention"
STATE_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def _heads(c):
    return (c["linear_num_value_heads"], c["linear_key_head_dim"],
            c["linear_value_head_dim"])


def conv_channels(c):
    n, dk, dv = _heads(c)
    return n * (2 * dk + dv)


def ffn_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def linear_mixer_params(c):
    """q | k | v, the output gate, beta, the decay, the output projection
    and the depthwise convolution; A_log, dt_bias and the norm's weight
    beside them (0.25 K)."""
    n, dk, dv = _heads(c)
    H = c["hidden_size"]
    return (H * conv_channels(c) + H * n * dv + 2 * H * n + n * dv * H
            + c["linear_conv_kernel_dim"] * conv_channels(c)
            + 2 * n + dv)


def full_mixer_params(c):
    H = c["hidden_size"]
    return 4 * H * H + 2 * H           # q | k | v, o, the two QK-norms


def layer_params(c, kind):
    mixer = linear_mixer_params(c) if kind == LINEAR \
        else full_mixer_params(c)
    return mixer + ffn_params(c) + 2 * c["hidden_size"]   # the two norms


def held_params(c):
    """Every parameter of the cut, embedding and untied head included."""
    return (sum(layer_params(c, kind) for kind in c["layer_types"])
            + 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"])


def kv_bytes_per_token(c, itemsize=2):
    """Keys and values one token caches over the full-attention layers."""
    full = sum(kind != LINEAR for kind in c["layer_types"])
    return full * 2 * c["hidden_size"] * itemsize


def state_bytes_per_slot(c, itemsize=2):
    """What one decode slot holds over the linear layers: the state in
    `state_dtype` and the convolution's last inputs."""
    n, dk, dv = _heads(c)
    linear = sum(kind == LINEAR for kind in c["layer_types"])
    state = n * dk * dv * STATE_ITEMSIZE[c["state_dtype"]]
    return linear * (state + (c["linear_conv_kernel_dim"] - 1)
                     * conv_channels(c) * itemsize)


def decode_step_bytes(c, live_tokens, slots, itemsize=2):
    """One decode step: every layer's parameters and the head once, the
    `slots` embedding rows looked up, the cached keys and values of the
    `live_tokens` attended to, and each slot's state read and written."""
    params = (held_params(c) - c["vocab_size"] * c["hidden_size"]
              + slots * c["hidden_size"])
    return (params * itemsize + live_tokens * kv_bytes_per_token(c, itemsize)
            + slots * 2 * state_bytes_per_slot(c, itemsize))


# -- the two kernels, a call ------------------------------------------------

def gdn_step_cost(c, slots, itemsize=2):
    """(operations, bytes) of one `gdn_step` call, one layer: per slot
    and head the state decayed (dk dv), S^T k (2 dk dv), the rank-1 write
    (2 dk dv) and S^T q (2 dk dv); the state read and written, q, k, v in,
    o out (float32), alpha and beta."""
    n, dk, dv = _heads(c)
    state = n * dk * dv
    ops = slots * 7 * state
    moved = slots * (2 * state * STATE_ITEMSIZE[c["state_dtype"]]
                     + n * (2 * dk + dv) * itemsize + n * dv * 4 + 2 * n * 4)
    return ops, moved


def gdn_chunk_cost(c, tokens, chunk=64):
    """(operations, bytes) of one `gdn_chunk_scan` kernel call, one layer,
    over `tokens` tokens (whole chunks): per chunk and head the three
    products with the carried state (w S, q S, k^T U: 2 C dk dv each) and
    the chunk's own attention (2 C C dv); what the call reads (w, u0, q,
    attn, k: float32) and writes (o, the final state). The state between
    chunks stays in VMEM and moves nothing."""
    n, dk, dv = _heads(c)
    chunks = -(-tokens // chunk)
    per_chunk = 3 * 2 * chunk * dk * dv + 2 * chunk * chunk * dv
    ops = n * chunks * per_chunk
    moved = 4 * n * (chunks * (3 * chunk * dk + 2 * chunk * dv
                               + chunk * chunk + 1) + dk * dv)
    return ops, moved
