"""Reference forward pass of an OLMo-hybrid decoder (`model_type:
olmo_hybrid`): plain `jax.numpy`, float32, matrix products at `highest`
precision, one sequence at a time, the linear layers' recurrence token by
token (`lax.scan`), no cache, no batching, no kernel, nothing imported
from the system under test.

`cfg` is a dict of the published config.json's keys (`layer_types` a
list: the mixer of each layer). `weights` maps the served model's
parameter names to arrays.

Block (the OLMo 2 / 3 reordered norm: a sublayer's OUTPUT is normalised
before the residual add, nothing before the sublayer):
    h = x + RMSNorm(Mixer(x));  y = h + RMSNorm(SwiGLU(h)).

`linear_attention` (gated delta rule; Yang, Kautz, Hatamizadeh, "Gated
Delta Networks", arXiv:2412.06464), n heads of key size dk, value size dv:
    [q~ | k~ | v~] = x W_qkv, each channel through a causal depthwise
    convolution over time (width `linear_conv_kernel_dim`, no bias) and
    SiLU; per head q = q~ / |q~| * dk^-1/2, k = k~ / |k~|;
    beta = 2 sigmoid(x W_b) (`linear_allow_neg_eigval`: beta in (0, 2));
    alpha = exp(-exp(A_log) softplus(x W_a + dt_bias));
    S_t = alpha_t S_{t-1};  u_t = beta_t (v_t - S_t^T k_t);
    S_t += k_t u_t^T;  o_t = S_t^T q_t;  S_0 = 0;
    y = (RMSNorm_dv(o) * SiLU(x W_g)) W_o.
`full_attention`: [q | k | v] = x W_qkv, RMSNorm over the whole width of
    q and of k (QK-norm) before the heads are split, causal softmax
    attention at head_dim^-1/2, W_o. No rotary embedding.

Departures from the published description, and what the source leaves
open (the configuration file lists the same under `assumed`):
- `rope_parameters.rope_theta` is null in the source: read to the letter,
  the full layers rotate nothing; order comes from the recurrent layers
  and their convolutions;
- the norm placement and the QK-norm are the OLMo 2 / 3 family's, which
  the row's config does not spell out;
- q, k and v (and the convolution's three weights) are stored as one
  matrix `mixer.qkv` [H, q | k | v] and one `mixer.conv` [taps, channels]:
  a fixed concatenation of the published layer's separate leaves;
- attention runs over `head_block` heads at a time so that the scores of
  a 5000-token sequence fit; the result is the same.
"""
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
