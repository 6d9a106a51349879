"""The benchmark's own copy of the LongCat-Flash reference and the weights
every run is made of. Nothing here imports the program (`paddle_tpu`).

MATHEMATICS (from `import jax` down to `forward`): a verbatim copy of
paddle_tpu/text/models/reference/longcat_flash.py, so that the program's
copy may change and the yardstick does not
(benchmark/tests/test_ref_longcat_flash.py holds the two together): plain
`jax.numpy`, float32, matrix products at `highest` precision, one
sequence at a time, keys and values always decompressed, no cache, no
batching, no kernel. A layer is a shortcut-connected expert block (two
latent attentions and two dense FFNs in sequence, the expert layer fed
from the first sublayer and added after the second); the router is a
softmax over routed AND zero-compute experts. Its departures from the
published description (rotary pairing; attention over `head_block` heads
at a time) and what it takes from the family's modelling code are listed
in that file's docstring.

WEIGHTS (below the copy): every leaf of the served share is drawn from
`--seed` on the device, one leaf at a time (`ref_kimi_k2.make_leaf`): matrices normal with std
`assumed.initializer_range`, the router's selection bias normal with std
`assumed.router_bias_std` (small against a softmax score over 768, so
that selection with a bias differs from selection without in a minority
of tokens: README_scmoe.md), norms at 1; matrices are rounded to the
configuration's dtype, which is what the program is given and what the
reference computes from (in float32). `make_weights` yields (name, array)
under the program's parameter names; the reference never holds more than
one block's float32 leaves at a time (`reference_logits`): a block is
1.243 B parameters = 5.0 GB in float32, the whole share 20.7 GB.
"""
import jax
import jax.numpy as jnp

# a leaf from (seed, index, shape, kind): the first share's own rule
from benchmark.lib.ref_kimi_k2 import make_leaf

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


# -- the weights ------------------------------------------------------------

def ref_config(config):
    """The reference's `cfg` from a configuration file: the published keys
    plus the router's width and the held range of `share`."""
    cfg = {k: config[k] for k in (
        "num_layers", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "q_lora_rank", "kv_lora_rank",
        "mla_scale_q_lora", "mla_scale_kv_lora", "rms_norm_eps",
        "rope_theta", "moe_topk", "routed_scaling_factor",
        "zero_expert_num")}
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
    D, W = config["ffn_hidden_size"], config["expert_ffn_hidden_size"]
    out = [("embed", (V, H), "matrix")]
    for i in range(config["num_layers"]):
        for j in (0, 1):
            p = f"blocks.{i}.sub.{j}."
            out += [(p + "attn_norm", (H,), "ones"),
                    (p + "ffn_norm", (H,), "ones"),
                    (p + "attn.q_a", (H, rq), "matrix"),
                    (p + "attn.q_norm", (rq,), "ones"),
                    (p + "attn.q_b", (rq, h * (dn + dr)), "matrix"),
                    (p + "attn.kv_a", (H, rkv + dr), "matrix"),
                    (p + "attn.kv_norm", (rkv,), "ones"),
                    (p + "attn.kv_b", (rkv, h * (dn + dv)), "matrix"),
                    (p + "attn.o", (h * dv, H), "matrix"),
                    (p + "ffn.gate", (H, D), "matrix"),
                    (p + "ffn.up", (H, D), "matrix"),
                    (p + "ffn.down", (D, H), "matrix")]
        p = f"blocks.{i}.experts."
        out += [(p + "router_weight", (H, E), "matrix"),
                (p + "router_bias", (E,), "bias"),
                (p + "gate", (held, H, W), "matrix"),
                (p + "up", (held, H, W), "matrix"),
                (p + "down", (held, W, H), "matrix")]
    return out + [("norm", (H,), "ones"), ("head", (H, V), "matrix")]


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
    first[k]+1.. — computed block by block: every sequence goes through
    block i before block i+1's weights are drawn, and a block's leaves
    are turned to float32 one at a time as they are drawn, so one block's
    float32 leaves (5.0 GB) are on the device at a time. Sequences are
    padded with id 0 to a common multiple of `pad_to` (causal attention:
    what follows a position cannot change it), so the block compiles
    once. -> [logits [s_k - 1 - first_k, vocab] float32 numpy]."""
    import numpy as np
    cfg, held = ref_config(config)
    s_max = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    pos = jnp.arange(s_max, dtype=jnp.int32)
    step = jax.jit(lambda w, x: block(w, cfg, x, pos, held))
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(dict(make_weights(
            seed, config, names=("embed",)))["embed"], F32)
        xs = []
        for ids in sequences:
            padded = np.zeros((s_max,), np.int32)
            padded[:len(ids)] = ids
            xs.append(embed[jnp.asarray(padded)])
        del embed
        for i in range(cfg["num_layers"]):
            prefix = f"blocks.{i}."
            w = {name[len(prefix):]: jnp.asarray(leaf, F32)
                 for name, leaf in make_weights(seed, config, prefix)}
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
