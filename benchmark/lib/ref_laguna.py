"""The benchmark's own copy of the Laguna reference and the weights every
run is made of. Nothing here imports the program (`paddle_tpu`).

MATHEMATICS (from `import math` down to `forward`): a verbatim copy of
paddle_tpu/text/models/reference/laguna.py, so that the program's copy may
change and the yardstick does not (benchmark/tests/test_ref_laguna.py
holds the two together): plain `jax.numpy`, float32, matrix products at
`highest` precision, one sequence at a time, dense masks, no cache, no
batching, no kernel, no tiles. Grouped-query attention with a sigmoid gate
a head; full layers under YaRN over half the head, sliding layers (the
last `sliding_window` keys) under plain rotary over all of it; a dense
SwiGLU in `mlp_only_layers`, elsewhere a softmax router over the whole
width, the chosen renormalised and scaled, summed over the experts that
are HELD, plus a shared expert. What it takes from the family's
convention and its departures from the published description are listed
in that file's docstring and in the configuration file (`assumed`,
`departures`).

WEIGHTS (below the copy): every leaf of the served share is drawn from
`--seed` on the device, one leaf at a time (`ref_kimi_k2.make_leaf`):
matrices normal with std `assumed.initializer_range`, norms at 1, the
router's selection bias zero (the family has none); matrices are rounded
to the configuration's dtype, which is what the program is given and what
the reference computes from (in float32). `make_weights` yields (name,
array) under the program's parameter names; the reference never holds
more than one block's float32 leaves at a time (`reference_logits`): a
sliding expert layer is 224.4 M parameters = 0.9 GB in float32, the whole
share 10.7 GB.
"""
import math

import jax
import jax.numpy as jnp

# a leaf from (seed, index, shape, kind): the first share's own rule
from benchmark.lib.ref_kimi_k2 import make_leaf

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


# -- the weights ------------------------------------------------------------

def ref_config(config):
    """(the reference's `cfg`, the held range) from a configuration file:
    the published keys, the router at its published width
    (`share.router_width`; the file's `num_experts` counts the experts
    held here)."""
    cfg = {k: config[k] for k in (
        "num_hidden_layers", "layer_types", "num_attention_heads_per_layer",
        "num_key_value_heads", "head_dim", "sliding_window",
        "rope_parameters", "mlp_only_layers", "num_experts_per_tok",
        "moe_routed_scaling_factor", "norm_topk_prob", "rms_norm_eps")}
    cfg["num_experts"] = int(config["share"]["router_width"])
    return cfg, tuple(config["share"]["experts_held"])


def leaf_shapes(config):
    """[(name, shape, kind)] of every leaf of the served share, in a fixed
    order; kind is "matrix", "ones" or "zeros". Names are the program's."""
    H, V = config["hidden_size"], config["vocab_size"]
    kv, d = config["num_key_value_heads"], config["head_dim"]
    E, held = config["share"]["router_width"], config["num_experts"]
    D, W = config["intermediate_size"], config["moe_intermediate_size"]
    S = config["shared_expert_intermediate_size"]
    out = [("embed", (V, H), "matrix")]
    for i in range(config["num_hidden_layers"]):
        n = config["num_attention_heads_per_layer"][i]
        p = f"blocks.{i}."
        out += [(p + "attn_norm", (H,), "ones"),
                (p + "attn.qkv", (H, (n + 2 * kv) * d), "matrix"),
                (p + "attn.g", (H, n), "matrix"),
                (p + "attn.o", (n * d, H), "matrix"),
                (p + "ffn_norm", (H,), "ones")]
        if i in config["mlp_only_layers"]:
            out += [(p + "ffn.gate", (H, D), "matrix"),
                    (p + "ffn.up", (H, D), "matrix"),
                    (p + "ffn.down", (D, H), "matrix")]
            continue
        out += [(p + "ffn.router_weight", (H, E), "matrix"),
                (p + "ffn.router_bias", (E,), "zeros"),
                (p + "ffn.gate", (held, H, W), "matrix"),
                (p + "ffn.up", (held, H, W), "matrix"),
                (p + "ffn.down", (held, W, H), "matrix"),
                (p + "ffn.shared_gate", (H, S), "matrix"),
                (p + "ffn.shared_up", (H, S), "matrix"),
                (p + "ffn.shared_down", (S, H), "matrix")]
    return out + [("norm", (H,), "ones"), ("head", (H, V), "matrix")]


def make_weights(seed, config, prefix="", names=None):
    """Yield (name, array) for every leaf whose name starts with `prefix`
    (and is in `names`, when given), drawn one at a time: the caller
    decides how many live at once."""
    for index, (name, shape, kind) in enumerate(leaf_shapes(config)):
        if name.startswith(prefix) and (names is None or name in names):
            yield name, (jnp.zeros(shape, F32) if kind == "zeros"
                         else make_leaf(seed, config, index, shape, kind))


def reference_logits(seed, config, sequences, first, pad_to=None,
                     q_block=2304):
    """The reference's logits for `sequences` (each ids [s]) at positions
    first[k]..s-2 of sequence k — the positions that predict its tokens
    first[k]+1.. — computed block by block: every sequence goes through
    block i before block i+1's weights are drawn, and a block's leaves
    are turned to float32 one at a time as they are drawn, so one block's
    float32 leaves (0.9 GB) are on the device at a time; the scores of
    one key-value head's group exist for `q_block` queries at a time
    (9 x 2304 x 9216 float32 = 0.76 GB). Sequences are padded with id 0
    to a common multiple of `pad_to` (causal attention: what follows a
    position cannot change it); by default to the longest stream the
    deployment admits (`serve.max_seq_len`), whatever the sample holds, so
    that each kind of block is ONE program in every run and a run's
    compile cache serves the next: a sample with a prompt of the 8192
    bucket pads to 9216 anyway, and three blocks of unrolled heads and
    query blocks at a new length cost minutes of compilation.
    -> [logits [s_k - 1 - first_k, vocab] float32 numpy]."""
    import numpy as np
    cfg, held = ref_config(config)
    pad_to = pad_to or int(config["serve"]["max_seq_len"])
    s_max = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    pos = jnp.arange(s_max, dtype=jnp.int32)
    steps = {}          # one program a kind of block

    def step_of(i):
        kind = (cfg["layer_types"][i],
                cfg["num_attention_heads_per_layer"][i],
                i in cfg["mlp_only_layers"])
        if kind not in steps:
            steps[kind] = jax.jit(lambda w, x: block(
                w, cfg, x, pos, i, held, head_block=1,
                q_block=q_block if s_max > q_block else None))
        return steps[kind]

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
            prefix = f"blocks.{i}."
            w = {name[len(prefix):]: jnp.asarray(leaf, F32)
                 for name, leaf in make_weights(seed, config, prefix)}
            xs = [step_of(i)(w, x) for x in xs]
            jax.block_until_ready(xs)
            del w
        top = {k: jnp.asarray(v, F32) for k, v in make_weights(
            seed, config, names=("norm", "head"))}
        out = []
        for ids, a, x in zip(sequences, first, xs):
            h = rms_norm(x[a:len(ids) - 1], top["norm"], cfg["rms_norm_eps"])
            out.append(np.asarray(h @ top["head"], np.float32))
    return out
