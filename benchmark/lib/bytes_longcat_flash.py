"""Bytes one decode step of a LongCat-Flash share must read from HBM, from
the configuration file's shapes: what the algorithm needs, not what a
program happens to move, so that bytes over (time x peak bandwidth)
cannot pass 100 %. A layer is two latent attentions, two dense FFNs, the
router and the routed experts held here; a zero-compute expert has no
parameters. Parameters are counted once a step (every slot shares them),
in the configuration's dtype; activations, the written latents and the
logits are left out (a few MB of ~10 GB)."""


def attention_params(c):
    """One latent attention (a layer has two)."""
    h = c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (c["hidden_size"] * c["q_lora_rank"]
            + c["q_lora_rank"] * h * qk
            + c["hidden_size"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * c["hidden_size"])


def dense_ffn_params(c):
    """One dense SwiGLU feed-forward (a layer has two)."""
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c):
    """One routed expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def router_params(c):
    """Routed and zero-compute experts alike have a router column."""
    return c["hidden_size"] * c["share"]["router_width"]


def layer_params(c, experts):
    """A shortcut-connected layer with `experts` routed experts read (all
    held: n_routed_experts; in a step: those that got a pair)."""
    return (2 * attention_params(c) + 2 * dense_ffn_params(c)
            + router_params(c) + experts * expert_params(c))


def held_params(c):
    """Every parameter of the share (norms and the selection bias left
    out, 0.1 M)."""
    return (c["num_layers"] * layer_params(c, c["n_routed_experts"])
            + 2 * c["vocab_size"] * c["hidden_size"])


def latent_bytes_per_token(c, itemsize=2):
    """What one token caches over all layers: two latents a layer."""
    return (2 * c["num_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize)


def decode_step_bytes(c, experts_touched, live_tokens, rows, itemsize=2):
    """One decode step: per layer two attentions, two dense FFNs, the
    router and the `experts_touched` (mean per layer-step) experts that
    got a pair, then the head, the `rows` embedding rows looked up, and
    the cached latents of the `live_tokens` attended to."""
    params = (c["num_layers"] * layer_params(c, experts_touched)
              + c["vocab_size"] * c["hidden_size"]
              + rows * c["hidden_size"])
    return params * itemsize + live_tokens * latent_bytes_per_token(
        c, itemsize)
