"""Reference forward pass of a Kimi-K2 / DeepSeek-V3 style decoder
(`model_type: kimi_k2`): plain `jax.numpy`, float32, matrix products at
`highest` precision, one sequence at a time, no cache (keys and values
are always decompressed), no batching, no kernel, nothing imported from
the system under test.

`cfg` is a dict of the published config.json's keys plus `router_width`
(how many experts the router scores; `n_routed_experts` when absent).
`weights` maps the served model's parameter names to arrays. `held` =
(first, count) is the contiguous range of routed experts whose weights
are present (`blocks.<i>.ffn.{gate,up,down}` hold `count` experts);
routing, top-k and the normalisation are over the router's full width,
the sum over the chosen experts that are held, the shared expert in
full. `held = (0, router_width)` is the uncut layer.

Departures from the published description:
- rotary pairing: the rotated slice pairs entry i with entry i + d/2
  (`rotate_half`); the published checkpoints store the pairs interleaved
  (2i, 2i+1) and permute before rotating. The two differ by a fixed
  permutation of the columns of `q_b`'s and `kv_a`'s rotary slices,
  which random weights cannot tell apart;
- text only: the family's vision tower is not part of the language
  model's config and is not here;
- attention runs over `head_block` heads at a time, so that the scores
  of a 3072-token sequence fit beside the weights; the result is the
  same.
"""
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(cfg):
    """Rotary frequencies [d/2]: theta^(-2i/d), under YaRN blended with
    the same divided by `factor` along the linear ramp between the
    correction dimensions of beta_fast and beta_slow."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    sc = cfg.get("rope_scaling")
    if not sc:
        return freq
    orig = float(sc["original_max_position_embeddings"])

    def correction_dim(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freq / float(sc["factor"]) * ramp + freq * (1.0 - ramp)


def softmax_scale(cfg):
    """(nope + rope)^-1/2 * m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1
    under YaRN. cos and sin are scaled by mscale / mscale_all_dim's m,
    which is 1 for the published mscale = mscale_all_dim."""
    sc = cfg.get("rope_scaling")
    m = yarn_mscale(float(sc["factor"]), sc.get("mscale_all_dim", 0)) \
        if sc else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def rope(x, pos, cfg):
    """x [s, ..., d] rotated by position; pairs (i, i + d/2)."""
    sc = cfg.get("rope_scaling")
    factor = yarn_mscale(float(sc["factor"]), sc.get("mscale", 1)) \
        / yarn_mscale(float(sc["factor"]), sc.get("mscale_all_dim", 0)) \
        if sc else 1.0
    ang = pos.astype(F32)[:, None] * inv_freq(cfg)[None]       # [s, d/2]
    ang = jnp.concatenate([ang, ang], axis=-1)
    ang = ang.reshape((ang.shape[0],) + (1,) * (x.ndim - 2) + ang.shape[1:])
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) * factor + rot * jnp.sin(ang) * factor


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(w, cfg, x, pos, head_block=8):
    """Multi-head latent attention over one sequence x [s, H], causal."""
    s = x.shape[0]
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = rms_norm(x @ w["attn.q_a"], w["attn.q_norm"], eps)
    q = (c_q @ w["attn.q_b"]).reshape(s, h, dn + dr)
    q_nope, q_r = q[..., :dn], rope(q[..., dn:], pos, cfg)
    kva = x @ w["attn.kv_a"]
    c_kv = rms_norm(kva[:, :rank], w["attn.kv_norm"], eps)
    k_r = rope(kva[:, rank:], pos, cfg)                         # [s, dr]
    kv = (c_kv @ w["attn.kv_b"]).reshape(s, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    causal = pos[None, :] <= pos[:, None]
    out = []
    for h0 in range(0, h, head_block):
        hs = slice(h0, h0 + head_block)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope[:, hs], k_nope[:, hs])
                  + jnp.einsum("qhd,kd->hqk", q_r[:, hs], k_r)) \
            * softmax_scale(cfg)
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:, hs]))
    return jnp.concatenate(out, axis=1).reshape(s, h * dv) @ w["attn.o"]


def route(w, cfg, x):
    """-> (expert ids [s, k] over the router's width, weights [s, k]):
    sigmoid scores; the k experts with the highest score + bias; weights
    the scores themselves, normalised over the k, scaled."""
    scores = jax.nn.sigmoid(x @ w["ffn.router_weight"])
    _, idx = jax.lax.top_k(scores + w["ffn.router_bias"],
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, cfg["routed_scaling_factor"] * chosen \
        / jnp.sum(chosen, axis=-1, keepdims=True)


def expert_layer(w, cfg, x, held):
    """Σ over the chosen experts that are held of weight * expert(x),
    plus the shared expert. Every held expert is applied to every token
    and weighted by zero where the token did not choose it."""
    idx, weights = route(w, cfg, x)
    first, count = held
    y = swiglu(x, w["ffn.shared_gate"], w["ffn.shared_up"],
               w["ffn.shared_down"])
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(x, w["ffn.gate"][e], w["ffn.up"][e],
                                      w["ffn.down"][e])
    return y


def block(w, cfg, x, pos, sparse, held, head_block=8):
    """One pre-norm block; `w` holds the block's leaves by their names
    inside it (`attn.q_a`, `ffn.gate`, ...)."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(w, cfg, rms_norm(x, w["attn_norm"], eps), pos,
                      head_block)
    f = rms_norm(h, w["ffn_norm"], eps)
    return h + (expert_layer(w, cfg, f, held) if sparse
                else swiglu(f, w["ffn.gate"], w["ffn.up"], w["ffn.down"]))


def block_weights(weights, i):
    """The leaves of block i, float32, keyed by their names inside it."""
    prefix = f"blocks.{i}."
    return {k[len(prefix):]: jnp.asarray(v, F32)
            for k, v in weights.items() if k.startswith(prefix)}


def forward(weights, cfg, ids, held=None):
    """Logits [s, vocab] of one sequence of ids [s]."""
    held = held or (0, cfg.get("router_width", cfg["n_routed_experts"]))
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        x = jnp.asarray(weights["embed"], F32)[ids]
        for i in range(cfg["num_hidden_layers"]):
            x = block(block_weights(weights, i), cfg, x, pos,
                      i >= cfg["first_k_dense_replace"], held)
        x = rms_norm(x, jnp.asarray(weights["norm"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(weights["head"], F32)
