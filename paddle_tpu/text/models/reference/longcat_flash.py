"""Reference forward pass of a LongCat-Flash style decoder
(`LongcatFlashForCausalLM`): plain `jax.numpy`, float32, matrix products
at `highest` precision, one sequence at a time, no cache (keys and values
are always decompressed), no batching, no kernel, nothing imported from
the system under test.

A layer is a shortcut-connected expert block: two sublayers in sequence,
each `h = x + MLA(RMSNorm(x)); f = RMSNorm(h); x = h + SwiGLU(f)`, and one
expert layer `m = MoE(f of the FIRST sublayer)` that is added to the
layer's output after the SECOND.

`cfg` is a dict of the published config.json's keys plus `router_width`
(how many outputs the router has: routed experts, then `zero_expert_num`
zero-compute experts). `weights` maps the served model's parameter names
to arrays. `held` = (first, count) is the contiguous range of routed
experts whose weights are present (`blocks.<i>.experts.{gate,up,down}`
hold `count` experts). The router's softmax runs over its whole width,
the top-k over score + bias likewise, a chosen expert's weight is
`routed_scaling_factor` x its score (NOT renormalised over the chosen);
the sum runs over the chosen routed experts that are held; a zero-compute
expert is the identity on the expert layer's input and every chosen one
is computed here in full. `held = (0, router_width - zero_expert_num)` is
the uncut layer.

Not in the published keys, taken from the family's modelling code: the
chosen weights are not renormalised, the router has no bias term beside
the selection bias, the activation is SiLU, the head is untied.

Departures from the published description:
- rotary pairing: the rotated slice pairs entry i with entry i + d/2
  (`rotate_half`); the published checkpoints store the pairs interleaved
  (2i, 2i+1) and permute before rotating. The two differ by a fixed
  permutation of the columns of `q_b`'s and `kv_a`'s rotary slices,
  which random weights cannot tell apart;
- attention runs over `head_block` heads at a time, so that the scores
  of a 3072-token sequence fit beside the weights; the result is the
  same.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def inv_freq(cfg):
    """Rotary frequencies [d/2]: theta^(-2i/d); no scaling in this family."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    return theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)


def rope(x, pos, cfg):
    """x [s, ..., d] rotated by position; pairs (i, i + d/2)."""
    ang = pos.astype(F32)[:, None] * inv_freq(cfg)[None]       # [s, d/2]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[1:])
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(w, cfg, x, pos, head_block=8):
    """Multi-head latent attention over one sequence x [s, H], causal;
    `w` holds one sublayer's attention leaves (`q_a`, `kv_b`, ...). The
    queries are scaled by (H / q_lora_rank)^1/2 and the normed latent by
    (H / kv_lora_rank)^1/2 where the configuration says so."""
    s, H = x.shape
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q_scale = (H / cfg["q_lora_rank"]) ** 0.5 \
        if cfg.get("mla_scale_q_lora") else 1.0
    kv_scale = (H / rank) ** 0.5 if cfg.get("mla_scale_kv_lora") else 1.0
    c_q = rms_norm(x @ w["q_a"], w["q_norm"], eps)
    q = (c_q @ w["q_b"]).reshape(s, h, dn + dr) * q_scale
    q_nope, q_r = q[..., :dn], rope(q[..., dn:], pos, cfg)
    kva = x @ w["kv_a"]
    c_kv = rms_norm(kva[:, :rank], w["kv_norm"], eps) * kv_scale
    k_r = rope(kva[:, rank:], pos, cfg)                         # [s, dr]
    kv = (c_kv @ w["kv_b"]).reshape(s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    causal = pos[None, :] <= pos[:, None]
    out = []
    for h0 in range(0, h, head_block):
        hs = slice(h0, h0 + head_block)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[:, hs], k_nope[:, hs])
                  + jnp.einsum("qhd,kd->hqk", q_r[:, hs], k_r)) \
            * (dn + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:, hs]))
    return jnp.concatenate(out, axis=1).reshape(s, h * dv) @ w["o"]


def route(w, cfg, x):
    """-> (expert ids [s, k] over the router's width, weights [s, k]):
    softmax over the whole width; the k experts with the highest score +
    bias; weights the scores themselves, scaled, not renormalised."""
    scores = jax.nn.softmax(x @ w["router_weight"], axis=-1)
    _, idx = jax.lax.top_k(scores + w["router_bias"], cfg["moe_topk"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, cfg["routed_scaling_factor"] * chosen


def expert_layer(w, cfg, x, held):
    """Σ over the chosen routed experts that are held of weight *
    expert(x), plus (Σ of the chosen zero-compute experts' weights) * x.
    The loop runs over the held ids: each is applied to every token and
    weighted by zero where the token did not choose it."""
    idx, weights = route(w, cfg, x)
    first, count = held
    routed = w["router_weight"].shape[1] - cfg["zero_expert_num"]
    y = jnp.sum(jnp.where(idx >= routed, weights, 0.0),
                axis=-1)[:, None] * x
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(x, w["gate"][e], w["up"][e],
                                      w["down"][e])
    return y


def sub_weights(w, prefix):
    """The leaves of `w` under `prefix`, keyed by what follows it."""
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def block(w, cfg, x, pos, held, head_block=8):
    """One shortcut-connected layer; `w` holds the layer's leaves by
    their names inside it (`sub.0.attn.q_a`, `experts.gate`, ...)."""
    eps = cfg["rms_norm_eps"]
    for i in (0, 1):
        h = x + attention(sub_weights(w, f"sub.{i}.attn."), cfg,
                          rms_norm(x, w[f"sub.{i}.attn_norm"], eps), pos,
                          head_block)
        f = rms_norm(h, w[f"sub.{i}.ffn_norm"], eps)
        if i == 0:
            m = expert_layer(sub_weights(w, "experts."), cfg, f, held)
        x = h + swiglu(f, w[f"sub.{i}.ffn.gate"], w[f"sub.{i}.ffn.up"],
                       w[f"sub.{i}.ffn.down"])
    return x + m


def block_weights(weights, i):
    """The leaves of block i, float32, keyed by their names inside it."""
    prefix = f"blocks.{i}."
    return {k[len(prefix):]: jnp.asarray(v, F32)
            for k, v in weights.items() if k.startswith(prefix)}


def forward(weights, cfg, ids, held=None):
    """Logits [s, vocab] of one sequence of ids [s]."""
    held = held or (0, cfg["router_width"] - cfg["zero_expert_num"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        x = jnp.asarray(weights["embed"], F32)[ids]
        for i in range(cfg["num_layers"]):
            x = block(block_weights(weights, i), cfg, x, pos, held)
        x = rms_norm(x, jnp.asarray(weights["norm"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(weights["head"], F32)
