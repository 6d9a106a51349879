"""The benchmark's own copy of the MiMo-V2-Flash reference and the weights
every run is made of. Nothing here imports the program (`paddle_tpu`).

MATHEMATICS (from `import jax` down to `forward`): a verbatim copy of
paddle_tpu/text/models/reference/mimo_v2.py, so that the program's copy
may change and the yardstick does not (benchmark/tests/test_ref_mimo_v2.py
holds the two together): plain `jax.numpy`, float32, matrix products at
`highest` precision, one sequence at a time, dense masks, no cache, no
batching, no kernel, no tiles. Grouped-query attention with keys of
`head_dim` over values of `v_head_dim`, 4 | 8 key-value heads by layer
kind, rotary over the first int(head_dim * partial_rotary_factor) dims at
the kind's own theta, the value scaled; sliding layers (the last
`sliding_window` keys) with a sink logit a query head in the softmax's
denominator; a dense SwiGLU where `moe_layer_freq` is 0, elsewhere a
sigmoid router over the whole width, the chosen renormalised, summed over
the experts that are HELD; no shared expert. What it takes from the
family's convention and its departures from the published description
are listed in that file's docstring and in the configuration file
(`assumed`, `departures`).

WEIGHTS (below the copy): every leaf of the served share is drawn from
`--seed` on the device, one leaf at a time (`ref_kimi_k2.make_leaf`):
matrices normal with std `assumed.initializer_range`, norms at 1, the
router's selection bias normal with std `assumed.router_bias_std`, the
sinks normal around `assumed.sink_mean` with std `assumed.sink_std`, both
float32; matrices are rounded to the configuration's dtype, which is what
the program is given and what the reference computes from (in float32).
`make_weights` yields (name, array) under the program's parameter names;
the reference never holds more than one block's float32 leaves at a time
(`reference_logits`): a sliding expert layer is 498.1 M parameters = 2.0
GB in float32, the whole share 13.7 GB.
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


# -- the weights ------------------------------------------------------------

# a leaf from (seed, index, shape, kind): the first share's own rule
from benchmark.lib.ref_kimi_k2 import make_leaf  # noqa: E402

SOURCE_KEYS = (
    "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
    "num_attention_heads", "num_key_value_heads", "swa_num_attention_heads",
    "swa_num_key_value_heads", "head_dim", "v_head_dim", "sliding_window",
    "rope_theta", "swa_rope_theta", "partial_rotary_factor",
    "attention_value_scale", "add_swa_attention_sink_bias",
    "add_full_attention_sink_bias", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "layernorm_epsilon")


def ref_config(config):
    """(the reference's `cfg`, the held range) from a configuration file:
    the published keys, the router at its published width
    (`share.router_width`; the file's `n_routed_experts` counts the
    experts held here)."""
    cfg = {k: config[k] for k in SOURCE_KEYS}
    cfg["n_routed_experts"] = int(config["share"]["router_width"])
    return cfg, tuple(config["share"]["experts_held"])


def leaf_shapes(config):
    """[(name, shape, kind)] of every leaf of the served share, in a fixed
    order; kind is "matrix", "ones", "bias" or "sinks". Names are the
    program's."""
    H, V = config["hidden_size"], config["vocab_size"]
    d, dv = config["head_dim"], config["v_head_dim"]
    E, held = config["share"]["router_width"], config["n_routed_experts"]
    D, W = config["intermediate_size"], config["moe_intermediate_size"]
    out = [("embed", (V, H), "matrix")]
    for i in range(config["num_hidden_layers"]):
        _, n, kv, _, has_sink = layer_shape(config, i)
        p = f"blocks.{i}."
        out += [(p + "attn_norm", (H,), "ones"),
                (p + "ffn_norm", (H,), "ones"),
                (p + "attn.qkv", (H, (n + kv) * d + kv * dv), "matrix"),
                (p + "attn.o", (n * dv, H), "matrix")]
        if has_sink:
            out.append((p + "attn.sinks", (n,), "sinks"))
        if not config["moe_layer_freq"][i]:
            out += [(p + "ffn.gate", (H, D), "matrix"),
                    (p + "ffn.up", (H, D), "matrix"),
                    (p + "ffn.down", (D, H), "matrix")]
            continue
        out += [(p + "ffn.router_weight", (H, E), "matrix"),
                (p + "ffn.router_bias", (E,), "bias"),
                (p + "ffn.gate", (held, H, W), "matrix"),
                (p + "ffn.up", (held, H, W), "matrix"),
                (p + "ffn.down", (held, W, H), "matrix")]
    return out + [("norm", (H,), "ones"), ("head", (H, V), "matrix")]


def make_weights(seed, config, prefix="", names=None):
    """Yield (name, array) for every leaf whose name starts with `prefix`
    (and is in `names`, when given), drawn one at a time: the caller
    decides how many live at once. The sinks are `make_leaf`'s float32
    "bias" draw (standard normal times `assumed.router_bias_std`) rescaled
    to `assumed.sink_std` around `assumed.sink_mean`."""
    a = config["assumed"]
    for index, (name, shape, kind) in enumerate(leaf_shapes(config)):
        if not name.startswith(prefix) or (names is not None
                                           and name not in names):
            continue
        if kind == "sinks":
            unit = make_leaf(seed, config, index, shape, "bias") \
                / float(a["router_bias_std"])
            yield name, float(a["sink_mean"]) + float(a["sink_std"]) * unit
        else:
            yield name, make_leaf(seed, config, index, shape, kind)


def reference_logits(seed, config, sequences, first, pad_to=None,
                     q_block=1024):
    """The reference's logits for `sequences` (each ids [s]) at positions
    first[k]..s-2 of sequence k — the positions that predict its tokens
    first[k]+1.. — computed block by block: every sequence goes through
    block i before block i+1's weights are drawn, and a block's leaves
    are turned to float32 one at a time as they are drawn, so one block's
    float32 leaves (2.0 GB) are on the device at a time; the scores of
    one key-value head's group exist for `q_block` queries at a time
    (16 x 1024 x 14336 float32 = 0.94 GB in a full layer). Sequences are
    padded with id 0 to a common multiple of `pad_to` (causal attention:
    what follows a position cannot change it); by default to the longest
    stream the deployment admits (`serve.max_seq_len`), whatever the
    sample holds, so that each kind of block is ONE program in every run
    and a run's compile cache serves the next.
    -> [logits [s_k - 1 - first_k, vocab] float32 numpy]."""
    import numpy as np
    cfg, held = ref_config(config)
    pad_to = pad_to or int(config["serve"]["max_seq_len"])
    s_max = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    pos = jnp.arange(s_max, dtype=jnp.int32)
    steps = {}          # one program a kind of block

    def step_of(i):
        kind = (cfg["hybrid_layer_pattern"][i], cfg["moe_layer_freq"][i])
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
            h = rms_norm(x[a:len(ids) - 1], top["norm"],
                         cfg["layernorm_epsilon"])
            out.append(np.asarray(h @ top["head"], np.float32))
    return out
