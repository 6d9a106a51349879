"""Bytes one decode step of a Kimi-K2 share must read from HBM, from the
configuration file's shapes: what the algorithm needs, not what a program
happens to move, so that bytes over (time x peak bandwidth) cannot pass
100 %. Parameters are counted once a step (every slot shares them), in
the configuration's dtype; activations, the written latent and the logits
are left out (a few MB of ~8 GB)."""


def attention_params(c):
    h = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (c["hidden_size"] * c["q_lora_rank"]
            + c["q_lora_rank"] * h * qk
            + c["hidden_size"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * c["hidden_size"])


def expert_params(c):
    """One routed (or shared) expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["share"]["router_width"]


def dense_layer_params(c):
    return attention_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layer_params(c, experts):
    """An expert layer with `experts` routed experts read (all held:
    n_routed_experts; in a step: those that got a pair)."""
    return (attention_params(c) + c["n_shared_experts"] * expert_params(c)
            + router_params(c) + experts * expert_params(c))


def held_params(c):
    """Every parameter of the share (norms left out, 0.1 M)."""
    dense = c["first_k_dense_replace"]
    return (dense * dense_layer_params(c)
            + (c["num_hidden_layers"] - dense)
            * expert_layer_params(c, c["n_routed_experts"])
            + 2 * c["vocab_size"] * c["hidden_size"])


def latent_bytes_per_token(c, itemsize=2):
    """What one token caches over all layers."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize)


def decode_step_bytes(c, experts_touched, live_tokens, rows, itemsize=2):
    """One decode step: the dense layers, per expert layer attention,
    shared expert, router and the `experts_touched` (mean per layer-step)
    experts that got a pair, the head, the `rows` embedding rows looked
    up, and the cached latents of the `live_tokens` attended to."""
    dense = c["first_k_dense_replace"]
    params = (dense * dense_layer_params(c)
              + (c["num_hidden_layers"] - dense)
              * expert_layer_params(c, experts_touched)
              + c["vocab_size"] * c["hidden_size"]
              + rows * c["hidden_size"])
    return params * itemsize + live_tokens * latent_bytes_per_token(
        c, itemsize)
