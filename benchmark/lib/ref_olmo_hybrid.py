"""The benchmark's own copy of the OLMo-hybrid reference and the weights
every run is made of. Nothing here imports the program (`paddle_tpu`).

MATHEMATICS (from `import jax` down to `forward`): a verbatim copy of
paddle_tpu/text/models/reference/olmo_hybrid.py, so that the program's
copy may change and the yardstick does not (benchmark/tests/
test_ref_olmo_hybrid.py holds the two together): plain `jax.numpy`,
float32, matrix products at `highest` precision, one sequence at a time,
the linear layers' recurrence token by token (`lax.scan`), no cache, no
batching, no kernel. Its departures from the published description (no
rotary embedding, the reordered norm and QK-norm of the family, q | k | v
as one matrix, attention over `head_block` heads at a time) are listed in
that file's docstring and in the configuration file.

WEIGHTS (below the copy): every leaf of the served cut is drawn from
`--seed` on the device, one leaf at a time: matrices normal with std
`assumed.initializer_range`, rounded to the configuration's dtype (what
the program is given and what the reference computes from, in float32);
norms at 1; `A_log` = log U(1, 16) and `dt_bias` = softplus^-1 of
exp(U(log 0.001, log 0.1)) a head, float32, as the gated-delta-net
layer's own initialiser draws them. `make_weights` yields (name, array)
under the program's parameter names; `reference_logits` never holds more
than one block's float32 leaves at a time: whole, the float32 weights are
16.4 GB.
"""
import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def causal_conv(x, w):
    """x [s, channels], w [taps, channels]: y_t = sum_j w_j x_{t-taps+1+j},
    zeros before the sequence."""
    taps, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(w[j] * padded[j:j + s] for j in range(taps))


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, token by token. q, k [s, n, dk], v [s, n, dv],
    alpha, beta [s, n] -> o [s, n, dv]."""
    def token(S, x):
        q, k, v, alpha, beta = x
        S = alpha[:, None, None] * S                       # [n, dk, dv]
        u = beta[:, None] * (v - jnp.einsum("nkv,nk->nv", S, k))
        S = S + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("nkv,nk->nv", S, q)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, S0, (q, k, v, alpha, beta))[1]


def linear_attention(w, cfg, x):
    s = x.shape[0]
    n, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    qkv = jax.nn.silu(causal_conv(x @ w["mixer.qkv"], w["mixer.conv"]))
    q = l2_norm(qkv[:, :n * dk].reshape(s, n, dk)) * dk ** -0.5
    k = l2_norm(qkv[:, n * dk:2 * n * dk].reshape(s, n, dk))
    v = qkv[:, 2 * n * dk:].reshape(s, n, dv)
    beta = jax.nn.sigmoid(x @ w["mixer.b"])
    if cfg.get("linear_allow_neg_eigval"):
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(w["mixer.A_log"])
                    * jax.nn.softplus(x @ w["mixer.a"] + w["mixer.dt_bias"]))
    o = delta_rule(q, k, v, alpha, beta)
    o = rms_norm(o, w["mixer.o_norm"], cfg["rms_norm_eps"])
    return (o.reshape(s, n * dv) * jax.nn.silu(x @ w["mixer.g"])) \
        @ w["mixer.o"]


def full_attention(w, cfg, x, head_block=6):
    s, H = x.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    d = H // h
    qkv = x @ w["mixer.qkv"]
    q = rms_norm(qkv[:, :H], w["mixer.q_norm"], eps).reshape(s, h, d)
    k = rms_norm(qkv[:, H:2 * H], w["mixer.k_norm"], eps).reshape(s, h, d)
    v = qkv[:, 2 * H:].reshape(s, h, d)
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    out = []
    for h0 in range(0, h, head_block):
        hs = slice(h0, h0 + head_block)
        scores = jnp.einsum("qhd,khd->hqk", q[:, hs], k[:, hs]) * d ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v[:, hs]))
    return jnp.concatenate(out, axis=1).reshape(s, H) @ w["mixer.o"]


def block(w, cfg, x, kind):
    """One block; `w` holds the block's leaves by their names inside it
    (`mixer.qkv`, `ffn.gate`, ...), `kind` its entry of `layer_types`."""
    eps = cfg["rms_norm_eps"]
    mixer = linear_attention if kind == "linear_attention" \
        else full_attention
    h = x + rms_norm(mixer(w, cfg, x), w["mixer_norm"], eps)
    return h + rms_norm(swiglu(h, w["ffn.gate"], w["ffn.up"],
                               w["ffn.down"]), w["ffn_norm"], eps)


def block_weights(weights, i):
    """The leaves of block i, float32, keyed by their names inside it."""
    prefix = f"blocks.{i}."
    return {k[len(prefix):]: jnp.asarray(v, F32)
            for k, v in weights.items() if k.startswith(prefix)}


def forward(weights, cfg, ids):
    """Logits [s, vocab] of one sequence of ids [s]."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embed"], F32)[jnp.asarray(ids, jnp.int32)]
        for i, kind in enumerate(cfg["layer_types"]):
            x = block(block_weights(weights, i), cfg, x, kind)
        x = rms_norm(x, jnp.asarray(weights["norm"], F32),
                     cfg["rms_norm_eps"])
        return x @ jnp.asarray(weights["head"], F32)


# --------------------------------------------------------------------------
# weights and the benchmark's interface (drivers/serve_open_loop_ref.py)
# --------------------------------------------------------------------------

LINEAR = "linear_attention"


def ref_config(config):
    """The reference's `cfg` from a configuration file: the published
    keys it reads."""
    return {k: config[k] for k in (
        "layer_types", "num_attention_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_allow_neg_eigval", "rms_norm_eps")}


def leaf_shapes(config):
    """[(name, shape, kind)] of every leaf of the served cut, in a fixed
    order; kind is "matrix", "ones", "a_log" or "dt_bias". Names are the
    program's."""
    H, V, W = (config["hidden_size"], config["vocab_size"],
               config["intermediate_size"])
    n, dk, dv = (config["linear_num_value_heads"],
                 config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    if config["linear_num_key_heads"] != n \
            or config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("grouped heads are not in this reference")
    channels = n * (2 * dk + dv)
    out = [("embed", (V, H), "matrix")]
    for i, kind in enumerate(config["layer_types"]):
        p = f"blocks.{i}."
        if kind == LINEAR:
            out += [(p + "mixer.qkv", (H, channels), "matrix"),
                    (p + "mixer.conv", (config["linear_conv_kernel_dim"],
                                        channels), "matrix"),
                    (p + "mixer.g", (H, n * dv), "matrix"),
                    (p + "mixer.b", (H, n), "matrix"),
                    (p + "mixer.a", (H, n), "matrix"),
                    (p + "mixer.A_log", (n,), "a_log"),
                    (p + "mixer.dt_bias", (n,), "dt_bias"),
                    (p + "mixer.o_norm", (dv,), "ones"),
                    (p + "mixer.o", (n * dv, H), "matrix")]
        else:
            out += [(p + "mixer.qkv", (H, 3 * H), "matrix"),
                    (p + "mixer.q_norm", (H,), "ones"),
                    (p + "mixer.k_norm", (H,), "ones"),
                    (p + "mixer.o", (H, H), "matrix")]
        out += [(p + "mixer_norm", (H,), "ones"),
                (p + "ffn.gate", (H, W), "matrix"),
                (p + "ffn.up", (H, W), "matrix"),
                (p + "ffn.down", (W, H), "matrix"),
                (p + "ffn_norm", (H,), "ones")]
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
    if kind == "a_log":
        return jnp.log(jax.random.uniform(_key(seed, index), shape, F32,
                                          1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(_key(seed, index), shape, F32,
                                        math.log(0.001), math.log(0.1)))
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
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
    to a common multiple of `pad_to` (every layer is causal: what follows
    a position cannot change it), so each kind of block compiles once.
    -> [logits [s_k - 1 - first_k, vocab] float32 numpy]."""
    import numpy as np
    cfg = ref_config(config)
    s_max = -(-max(len(s) for s in sequences) // pad_to) * pad_to
    steps = {kind: jax.jit(functools.partial(block, cfg=cfg, kind=kind))
             for kind in set(cfg["layer_types"])}
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(dict(make_weights(
            seed, config, names=("embed",)))["embed"], F32)
        xs = []
        for ids in sequences:
            padded = np.zeros((s_max,), np.int32)
            padded[:len(ids)] = ids
            xs.append(embed[jnp.asarray(padded)])
        del embed
        for i, kind in enumerate(cfg["layer_types"]):
            w = block_weights(dict(make_weights(seed, config,
                                                f"blocks.{i}.")), i)
            xs = [steps[kind](w, x=x) for x in xs]
            jax.block_until_ready(xs)
            del w
        top = {k: jnp.asarray(v, F32) for k, v in make_weights(
            seed, config, names=("norm", "head"))}
        out = []
        for ids, a, x in zip(sequences, first, xs):
            h = rms_norm(x[a:len(ids) - 1], top["norm"], cfg["rms_norm_eps"])
            out.append(np.asarray(h @ top["head"], np.float32))
    return out
