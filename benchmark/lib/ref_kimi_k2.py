"""The benchmark's own copy of the Kimi-K2 reference and the weights every
run is made of. Nothing here imports the program (`paddle_tpu`).

MATHEMATICS (from `import math` down to `forward`): a verbatim copy of
paddle_tpu/text/models/reference/kimi_k2.py, so that the program's copy may
change and the yardstick does not (benchmark/tests/test_ref_kimi_k2.py
holds the two together): plain `jax.numpy`, float32, matrix products at
`highest` precision, one sequence at a time, keys and values always
decompressed, no cache, no batching, no kernel. Its departures from the
published description (rotary pairing; text only; attention over
`head_block` heads at a time) are listed in that file's docstring.

WEIGHTS (below the copy): every leaf of the served share is drawn from
`--seed` on the device, one leaf at a time: matrices normal with std
`assumed.initializer_range`, the router's selection bias normal with std
`assumed.router_bias_std` (so that selection with a bias is what is
compared), norms at 1; matrices are rounded to the configuration's dtype,
which is what the program is given and what the reference computes from
(in float32). `make_weights` yields (name, array) under the program's
parameter names; `block_leaves` gives one block's leaves in float32 for
the reference, which never holds more than one block at a time
(`reference_logits`): whole, the float32 weights are 19.4 GB.
"""
import functools
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


# -- the weights ------------------------------------------------------------

def ref_config(config):
    """The reference's `cfg` from a configuration file: the published keys
    plus the router's width and the held range of `share`."""
    cfg = {k: config[k] for k in (
        "num_hidden_layers", "first_k_dense_replace", "num_attention_heads",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "rms_norm_eps", "rope_theta", "rope_scaling", "num_experts_per_tok",
        "routed_scaling_factor", "n_routed_experts")}
    cfg["router_width"] = int(config["share"]["router_width"])
    return cfg, tuple(config["share"]["experts_held"])


def leaf_shapes(config):
    """[(name, shape, kind)] of every leaf of the served share, in a fixed
    order; kind is "matrix", "ones" or "bias". Names are the program's."""
    H, V = config["hidden_size"], config["vocab_size"]
    h = config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    E, held = config["share"]["router_width"], config["n_routed_experts"]
    W = config["moe_intermediate_size"]
    shared = config["n_shared_experts"] * W
    out = [("embed", (V, H), "matrix")]
    for i in range(config["num_hidden_layers"]):
        p = f"blocks.{i}."
        out += [(p + "attn_norm", (H,), "ones"),
                (p + "ffn_norm", (H,), "ones"),
                (p + "attn.q_a", (H, rq), "matrix"),
                (p + "attn.q_norm", (rq,), "ones"),
                (p + "attn.q_b", (rq, h * (dn + dr)), "matrix"),
                (p + "attn.kv_a", (H, rkv + dr), "matrix"),
                (p + "attn.kv_norm", (rkv,), "ones"),
                (p + "attn.kv_b", (rkv, h * (dn + dv)), "matrix"),
                (p + "attn.o", (h * dv, H), "matrix")]
        if i < config["first_k_dense_replace"]:
            D = config["intermediate_size"]
            out += [(p + "ffn.gate", (H, D), "matrix"),
                    (p + "ffn.up", (H, D), "matrix"),
                    (p + "ffn.down", (D, H), "matrix")]
        else:
            out += [(p + "ffn.router_weight", (H, E), "matrix"),
                    (p + "ffn.router_bias", (E,), "bias"),
                    (p + "ffn.gate", (held, H, W), "matrix"),
                    (p + "ffn.up", (held, H, W), "matrix"),
                    (p + "ffn.down", (held, W, H), "matrix"),
                    (p + "ffn.shared_gate", (H, shared), "matrix"),
                    (p + "ffn.shared_up", (H, shared), "matrix"),
                    (p + "ffn.shared_down", (shared, H), "matrix")]
    return out + [("norm", (H,), "ones"), ("head", (H, V), "matrix")]


def _key(seed, index):
    """`--seed` is any whole number to a little over 2**31: both halves
    are folded in, then the leaf's index."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, index)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def make_leaf(seed, config, index, shape, kind):
    """Leaf number `index` of `leaf_shapes`, on the device."""
    dtype = jnp.dtype(config["dtype"])
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "bias":
        return _draw(_key(seed, index), shape,
                     float(config["assumed"]["router_bias_std"]), F32)
    return _draw(_key(seed, index), shape,
                 float(config["assumed"]["initializer_range"]), dtype)


def make_weights(seed, config, prefix="", names=None):
    """Yield (name, array) for every leaf whose name starts with `prefix`
    (and is in `names`, when given), drawn one at a time: the caller
    decides how many live at once."""
    for index, (name, shape, kind) in enumerate(leaf_shapes(config)):
        if name.startswith(prefix) and (names is None or name in names):
            yield name, make_leaf(seed, config, index, shape, kind)


def reference_logits(seed, config, sequences, first, pad_to=512):
    """The reference's logits for `sequences` (each ids [s]) at positions
    first[k]..s-2 of sequence k — the positions that predict its tokens
    first[k]+1.. — computed layer by layer: every sequence goes through
    block i before block i+1's weights are drawn, so one block's float32
    leaves are on the device at a time. Sequences are padded with id 0
    to a common multiple of `pad_to` (causal attention: what follows a
    position cannot change it), so each block compiles once.
    -> [logits [s_k - 1 - first_k, vocab] float32 numpy]."""
    import numpy as np
    cfg, held = ref_config(config)
    s_max = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    pos = jnp.arange(s_max, dtype=jnp.int32)
    dense = jax.jit(lambda w, x: block(w, cfg, x, pos, False, held))
    sparse = jax.jit(lambda w, x: block(w, cfg, x, pos, True, held))
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(dict(make_weights(
            seed, config, names=("embed",)))["embed"], F32)
        xs = []
        for ids in sequences:
            padded = np.zeros((s_max,), np.int32)
            padded[:len(ids)] = ids
            xs.append(embed[jnp.asarray(padded)])
        del embed
        for i in range(cfg["num_hidden_layers"]):
            w = block_weights(dict(make_weights(seed, config,
                                                f"blocks.{i}.")), i)
            step = sparse if i >= cfg["first_k_dense_replace"] else dense
            xs = [step(w, x) for x in xs]
            jax.block_until_ready(xs)
            del w
        top = {k: jnp.asarray(v, F32) for k, v in make_weights(
            seed, config, names=("norm", "head"))}
        out = []
        for ids, a, x in zip(sequences, first, xs):
            h = rms_norm(x[a:len(ids) - 1], top["norm"], cfg["rms_norm_eps"])
            out.append(np.asarray(h @ top["head"], np.float32))
    return out
