"""Reference forward pass of a Laguna style decoder (`model_type:
laguna`): plain `jax.numpy`, float32, matrix products at `highest`
precision, one sequence at a time, dense masks, no cache, no batching, no
kernel, no tiles, nothing imported from the system under test.

Layer l, x [s, H] the residual stream, n_l =
`num_attention_heads_per_layer[l]` query heads over `num_key_value_heads`
key-value heads of `head_dim`, no biases:

    a = RMSNorm(x);  [q | k | v] = a W_qkv;  g = sigmoid(a W_g)  [s, n_l]
    rotary on q and k, entry i paired with i + r/2 over the first r dims:
      full layers     r = head_dim * partial_rotary_factor, YaRN
                      (frequencies blended between extrapolation and
                      interpolation by `factor` along the ramp between the
                      correction dimensions of beta_fast and beta_slow),
                      cos and sin times `attention_factor`;
      sliding layers  r = head_dim * their own factor, their own theta,
                      no scaling
    query head j reads key-value head j // (n_l / kv); scores q.k /
    sqrt(head_dim); softmax over keys j <= i (full) or i - window < j <= i
    (sliding: `sliding_window` keys, itself included);
    h1 = x + concat_j(g_j o_j) W_o
    b = RMSNorm(h1)
    layer in `mlp_only_layers`:  m = SwiGLU(b)
    every other layer:  s = softmax(b W_r) over the router's whole width;
      the `num_experts_per_tok` highest; w = moe_routed_scaling_factor *
      s_chosen / sum(s_chosen); m = sum over the chosen experts that are
      HELD of w_e SwiGLU_e(b), plus SwiGLU_shared(b)
    x <- h1 + m
After the last layer RMSNorm and an untied head.

`cfg` is a dict of the published config.json's keys (`num_hidden_layers`
layers are run: the per-layer lists are read up to it). `weights` maps
the served model's parameter names to arrays. `held` = (first, count) is
the contiguous range of routed experts whose weights are present
(`blocks.<i>.ffn.{gate,up,down}` hold `count` experts); the router's
softmax, the top-k and the renormalisation run over its whole width, the
sum over the chosen experts that are held. `held = (0, num_experts)` is
the uncut layer.

Not in the published keys, set by the family's convention (the
configuration file lists each under `assumed`): pre-norm residual order
and no QK-norm (no key names one); the gate is the head-wise sigmoid gate
of "Gated Attention for Large Language Models" (arXiv:2505.06708),
computed from the normed input and applied before W_o (`gating:
"per-head"`); the router's scores are a softmax (the keys `norm_topk_prob`,
`shared_expert_intermediate_size`, `decoder_sparse_step` are the
Qwen2-MoE family's), renormalised over the chosen and then scaled; the
shared expert is added ungated; no selection bias
(`moe_router_logit_softcapping` 0 = off); SiLU.

Departures from the published description:
- rotary pairing: the rotated slice pairs entry i with entry i + r/2
  (`rotate_half`), which is what the family's code does for this
  `rope_type`; checkpoints that store pairs interleaved differ by a fixed
  permutation of W_qkv's columns, which random weights cannot tell apart;
- attention runs over `head_block` key-value heads and `q_block` queries
  at a time, so that the scores of a 9216-token sequence fit beside the
  weights; the result is the same;
- text only, greedy decoding; nothing stands in for the other chips of a
  deployment.
"""
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
FULL, SLIDING = "full_attention", "sliding_attention"


def inv_freq(p, head_dim):
    """(r, rotary frequencies [r/2], factor on cos and sin) of one layer
    type's `rope_parameters` entry."""
    r = int(round(head_dim * float(p.get("partial_rotary_factor", 1))))
    theta = float(p["rope_theta"])
    freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    if p.get("rope_type") != "yarn":
        return r, freq, 1.0
    factor = float(p["factor"])
    orig = float(p["original_max_position_embeddings"])

    def correction_dim(rotations):
        return r * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(p["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(p["beta_slow"]))), r - 1)
    ramp = jnp.clip((jnp.arange(r // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    blended = freq / factor * ramp + freq * (1.0 - ramp)
    return r, blended, float(p.get("attention_factor",
                                   0.1 * math.log(factor) + 1.0))


def rope(x, pos, p):
    """x [s, n, d]: the first r dims of every head rotated by position,
    pairs (i, i + r/2); the rest passes through."""
    r, freq, factor = inv_freq(p, x.shape[-1])
    ang = pos.astype(F32)[:, None] * freq[None]                # [s, r/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None]        # [s, 1, r]
    head, rest = x[..., :r], x[..., r:]
    rot = jnp.concatenate([-head[..., r // 2:], head[..., :r // 2]], axis=-1)
    turned = head * (jnp.cos(ang) * factor) + rot * (jnp.sin(ang) * factor)
    return jnp.concatenate([turned, rest], axis=-1)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(w, cfg, a, pos, kind, heads, head_block=2, q_block=None):
    """Gated grouped-query attention over one normed sequence a [s, H];
    `w` holds the layer's attention leaves (`qkv`, `g`, `o`). The scores
    exist for `head_block` key-value heads (with their groups) and
    `q_block` queries (None: all) at a time, each under its rows of the
    dense mask."""
    s = a.shape[0]
    kv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    group = heads // kv
    p = cfg["rope_parameters"][kind]
    qkv = a @ w["qkv"]
    gate = jax.nn.sigmoid(a @ w["g"])                           # [s, n]
    q = rope(qkv[:, :heads * d].reshape(s, heads, d), pos, p)
    k = rope(qkv[:, heads * d:(heads + kv) * d].reshape(s, kv, d), pos, p)
    v = qkv[:, (heads + kv) * d:].reshape(s, kv, d)
    seen = pos[None, :] <= pos[:, None]
    if kind == SLIDING:
        seen = seen & (pos[None, :] > pos[:, None] - cfg["sliding_window"])
    q = q.reshape(s, kv, group, d)
    rows = []
    for q0 in range(0, s, q_block or s):
        qs, out = slice(q0, q0 + (q_block or s)), []
        for h0 in range(0, kv, head_block):
            hs = slice(h0, h0 + head_block)
            scores = jnp.einsum("qhgd,khd->hgqk", q[qs, hs], k[:, hs]) \
                * d ** -0.5
            prob = jax.nn.softmax(
                jnp.where(seen[None, None, qs], scores, -jnp.inf), axis=-1)
            out.append(jnp.einsum("hgqk,khd->qhgd", prob, v[:, hs]))
        rows.append(jnp.concatenate(out, axis=1))
    o = jnp.concatenate(rows, axis=0).reshape(s, heads, d) * gate[..., None]
    return o.reshape(s, heads * d) @ w["o"]


def route(w, cfg, x):
    """-> (expert ids [s, k] over the router's width, weights [s, k]):
    softmax over the whole width; the k highest; weights renormalised
    over the chosen (`norm_topk_prob`), then scaled."""
    scores = jax.nn.softmax(x @ w["router_weight"], axis=-1)
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = cfg["moe_routed_scaling_factor"] * chosen
    if cfg.get("norm_topk_prob", True):
        weights = weights / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, weights


def routed_part(w, cfg, x, held):
    """Sum over the chosen experts that are held of weight * expert(x).
    The loop runs over the held ids: each is applied to every token and
    weighted by zero where the token did not choose it."""
    idx, weights = route(w, cfg, x)
    first, count = held
    y = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(x, w["gate"][e], w["up"][e],
                                      w["down"][e])
    return y


def shared_part(w, x):
    return swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])


def sub_weights(w, prefix):
    """The leaves of `w` under `prefix`, keyed by what follows it."""
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def block(w, cfg, x, pos, index, held, head_block=2, q_block=None):
    """Layer `index`; `w` holds the layer's leaves by their names inside
    it (`attn.qkv`, `ffn.gate`, ...)."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(sub_weights(w, "attn."), cfg,
                      rms_norm(x, w["attn_norm"], eps), pos,
                      cfg["layer_types"][index],
                      cfg["num_attention_heads_per_layer"][index],
                      head_block, q_block)
    f = rms_norm(h, w["ffn_norm"], eps)
    ffn = sub_weights(w, "ffn.")
    if index in cfg["mlp_only_layers"]:
        return h + swiglu(f, ffn["gate"], ffn["up"], ffn["down"])
    return h + routed_part(ffn, cfg, f, held) + shared_part(ffn, f)


def block_weights(weights, i):
    """The leaves of block i, float32, keyed by their names inside it."""
    prefix = f"blocks.{i}."
    return {k[len(prefix):]: jnp.asarray(v, F32)
            for k, v in weights.items() if k.startswith(prefix)}


def forward(weights, cfg, ids, held=None):
    """Logits [s, vocab] of one sequence of ids [s]."""
    held = held or (0, cfg["num_experts"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        x = jnp.asarray(weights["embed"], F32)[ids]
        for i in range(cfg["num_hidden_layers"]):
            x = block(block_weights(weights, i), cfg, x, pos, i, held)
        x = rms_norm(x, jnp.asarray(weights["norm"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(weights["head"], F32)
