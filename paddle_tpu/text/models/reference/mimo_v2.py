"""Reference forward pass of a MiMo-V2-Flash style decoder (`model_type:
mimo_v2_flash`): plain `jax.numpy`, float32, matrix products at `highest`
precision, one sequence at a time, dense masks, no cache, no batching, no
kernel, no tiles, nothing imported from the system under test.

Layer i of kind full | sliding (`hybrid_layer_pattern[i]` 0 | 1), x [s, H]
the residual stream, n query heads over hk key-value heads
(`num_attention_heads` over `num_key_value_heads` in a full layer,
`swa_num_attention_heads` over `swa_num_key_value_heads` in a sliding
one), keys and queries `head_dim` deep, values `v_head_dim`, no biases:

    a = RMSNorm(x);  [q | k | v] = a W_qkv
    v <- `attention_value_scale` * v
    rotary on q and k over the first int(head_dim * partial_rotary_factor)
      dims of a head, entry i paired with i + r/2, theta `rope_theta`
      (full) | `swa_rope_theta` (sliding), no scaling; the rest unrotated
    query head j reads key-value head j // (n / hk); scores q.k /
    sqrt(head_dim) over keys t <= i (full) or i - window < t <= i (sliding:
    `sliding_window` keys, itself included)
    full:     p = softmax(scores)
    sliding:  p_t = exp(s_t) / (exp(b_j) + sum_t' exp(s_t')), b_j the
              head's learned sink logit (`add_swa_attention_sink_bias`):
              a term of the denominator that has no value
    h1 = x + concat_j(sum_t p_t v_t) W_o
    b = RMSNorm(h1)
    `moe_layer_freq[i]` 0:  m = SwiGLU(b), `intermediate_size` wide
    `moe_layer_freq[i]` 1:  c = sigmoid(b W_r) over the router's whole
      width; the `num_experts_per_tok` highest of c + bias; w = c_chosen /
      sum(c_chosen) (`norm_topk_prob`), times `routed_scaling_factor`
      (null in the source: 1); m = sum over the chosen experts that are
      HELD of w_e SwiGLU_e(b); NO shared expert
    x <- h1 + m
After the last layer RMSNorm and an untied head.

`cfg` is a dict of the published config.json's keys (`num_hidden_layers`
layers are run: the per-layer lists are read up to it; `n_routed_experts`
is the ROUTER's width). `weights` maps the served model's parameter names
to arrays. `held` = (first, count) is the contiguous range of routed
experts whose weights are present (`blocks.<i>.ffn.{gate,up,down}` hold
`count` experts); the router's scores, the top-k and the renormalisation
run over its whole width, the sum over the chosen experts that are held.
`held = (0, n_routed_experts)` is the uncut layer.

Not in the published keys, set by the family's convention (the
configuration file lists each under `assumed`): pre-norm residual order,
no QK-norm, no attention bias (`attention_bias` false); the value scale
multiplies v after its projection (a scalar on v or on the head's output
is the same number but for rounding); the rotated dims are the FIRST r of
a head; the sink is a logit a query head in the softmax's denominator
(`add_swa_attention_sink_bias`; the gpt-oss form), none in the full layers
(`add_full_attention_sink_bias` false); the router is DeepSeek-V3's
(`scoring_func` sigmoid, `topk_method` noaux_tc: a selection bias that
only picks; `n_group` = `topk_group` = 1: no groups); SiLU.

Departures from the published description:
- rotary pairing: the rotated slice pairs entry i with entry i + r/2
  (`rotate_half`); checkpoints that store pairs interleaved differ by a
  fixed permutation of W_qkv's columns, which random weights cannot tell
  apart;
- the three multi-token-prediction layers that the family's description
  names are not here (no key of `config` names them);
- attention runs over `head_block` key-value heads and `q_block` queries
  at a time, one block after another, so that the scores of a
  14336-token sequence fit beside the weights; the result is the same;
- text only, greedy decoding; nothing stands in for the other chips of a
  deployment.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def layer_shape(cfg, index):
    """(sliding?, query heads, key-value heads, rotary theta, has a sink)
    of layer `index`."""
    if cfg["hybrid_layer_pattern"][index]:
        return (True, cfg["swa_num_attention_heads"],
                cfg["swa_num_key_value_heads"], float(cfg["swa_rope_theta"]),
                bool(cfg["add_swa_attention_sink_bias"]))
    return (False, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            float(cfg["rope_theta"]),
            bool(cfg["add_full_attention_sink_bias"]))


def rope(x, pos, theta, factor):
    """x [s, n, d]: the first r = int(d * factor) dims of every head
    rotated by position, pairs (i, i + r/2); the rest passes through."""
    r = int(x.shape[-1] * factor)
    freq = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = pos.astype(F32)[:, None] * freq[None]                # [s, r/2]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None]        # [s, 1, r]
    head, rest = x[..., :r], x[..., r:]
    rot = jnp.concatenate([-head[..., r // 2:], head[..., :r // 2]], axis=-1)
    return jnp.concatenate([head * jnp.cos(ang) + rot * jnp.sin(ang), rest],
                           axis=-1)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention(w, cfg, a, pos, index, head_block=2, q_block=None):
    """Grouped-query attention over one normed sequence a [s, H]; `w`
    holds the layer's attention leaves (`qkv`, `o`, and `sinks` where the
    layer has them). The scores exist for `head_block` key-value heads
    (with their groups) and `q_block` queries (None, or no divisor of s:
    all) at a time, each under its rows of the dense mask, one such block
    after another (`jax.lax.map`: a 14336-token sequence's scores, all
    alive at once, are 50 GB)."""
    s = a.shape[0]
    sliding, heads, kv, theta, has_sink = layer_shape(cfg, index)
    d, dv = cfg["head_dim"], cfg["v_head_dim"]
    group = heads // kv
    factor = cfg["partial_rotary_factor"]
    qkv = a @ w["qkv"]
    q = rope(qkv[:, :heads * d].reshape(s, heads, d), pos, theta, factor)
    k = rope(qkv[:, heads * d:(heads + kv) * d].reshape(s, kv, d), pos,
             theta, factor)
    v = qkv[:, (heads + kv) * d:].reshape(s, kv, dv) \
        * cfg["attention_value_scale"]
    seen = pos[None, :] <= pos[:, None]
    if sliding:
        seen = seen & (pos[None, :] > pos[:, None] - cfg["sliding_window"])
    hb = head_block if kv % head_block == 0 else 1
    qb = q_block if q_block and s % q_block == 0 else s
    # [head blocks, ...]: the queries [.., query blocks, qb, hb, G, d]
    q = jnp.transpose(q.reshape(s // qb, qb, kv // hb, hb, group, d),
                      (2, 0, 1, 3, 4, 5))
    k = jnp.transpose(k.reshape(s, kv // hb, hb, d), (1, 0, 2, 3))
    v = jnp.transpose(v.reshape(s, kv // hb, hb, dv), (1, 0, 2, 3))
    sinks = (w["sinks"] if has_sink else jnp.zeros((heads,), F32)) \
        .reshape(kv // hb, hb, group)

    def of_heads(args):
        q_h, k_h, v_h, sink_h = args

        def of_rows(rows):
            q_r, seen_r = rows                       # [qb, hb, G, d], [qb, s]
            scores = jnp.einsum("qhgd,khd->hgqk", q_r, k_h) * d ** -0.5
            scores = jnp.where(seen_r[None, None], scores, -jnp.inf)
            if has_sink:            # one more column, dropped after
                sink = jnp.broadcast_to(sink_h[:, :, None, None],
                                        scores.shape[:3] + (1,))
                scores = jnp.concatenate([scores, sink], axis=-1)
            prob = jax.nn.softmax(scores, axis=-1)[..., :s]
            return jnp.einsum("hgqk,khd->qhgd", prob, v_h)

        return jax.lax.map(of_rows, (q_h, seen.reshape(s // qb, qb, s)))

    out = jax.lax.map(of_heads, (q, k, v, sinks))    # [., ., qb, hb, G, dv]
    o = jnp.transpose(out, (1, 2, 0, 3, 4, 5)).reshape(s, heads * dv)
    return o @ w["o"]


def route(w, cfg, x):
    """-> (expert ids [s, k] over the router's width, weights [s, k]):
    sigmoid scores; the k highest of score + bias; weights the scores of
    the chosen, renormalised over them (`norm_topk_prob`), times the
    factor (null: 1)."""
    scores = jax.nn.sigmoid(x @ w["router_weight"])
    _, idx = jax.lax.top_k(scores + w["router_bias"],
                           cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = (cfg.get("routed_scaling_factor") or 1.0) * chosen
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


def sub_weights(w, prefix):
    """The leaves of `w` under `prefix`, keyed by what follows it."""
    return {k[len(prefix):]: v for k, v in w.items() if k.startswith(prefix)}


def block(w, cfg, x, pos, index, held, head_block=2, q_block=None):
    """Layer `index`; `w` holds the layer's leaves by their names inside
    it (`attn.qkv`, `ffn.gate`, ...)."""
    eps = cfg["layernorm_epsilon"]
    h = x + attention(sub_weights(w, "attn."), cfg,
                      rms_norm(x, w["attn_norm"], eps), pos, index,
                      head_block, q_block)
    f = rms_norm(h, w["ffn_norm"], eps)
    ffn = sub_weights(w, "ffn.")
    if not cfg["moe_layer_freq"][index]:
        return h + swiglu(f, ffn["gate"], ffn["up"], ffn["down"])
    return h + routed_part(ffn, cfg, f, held)


def block_weights(weights, i):
    """The leaves of block i, float32, keyed by their names inside it."""
    prefix = f"blocks.{i}."
    return {k[len(prefix):]: jnp.asarray(v, F32)
            for k, v in weights.items() if k.startswith(prefix)}


def forward(weights, cfg, ids, held=None):
    """Logits [s, vocab] of one sequence of ids [s]."""
    held = held or (0, cfg["n_routed_experts"])
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        pos = jnp.arange(ids.shape[0], dtype=jnp.int32)
        x = jnp.asarray(weights["embed"], F32)[ids]
        for i in range(cfg["num_hidden_layers"]):
            x = block(block_weights(weights, i), cfg, x, pos, i, held)
        x = rms_norm(x, jnp.asarray(weights["norm"], F32),
                     cfg["layernorm_epsilon"])
        return x @ jnp.asarray(weights["head"], F32)
