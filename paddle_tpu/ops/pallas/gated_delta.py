"""Gated delta-rule linear attention (Yang, Kautz, Hatamizadeh, "Gated
Delta Networks", arXiv:2412.06464): per head a state S [dk, dv] that every
token decays, corrects along its key and reads with its query,

    S_t = alpha_t S_{t-1};  u_t = beta_t (v_t - S_t^T k_t);
    S_t += k_t u_t^T;       o_t = S_t^T q_t,

alpha_t = exp(g_t) in (0, 1], beta_t in [0, 2). A token with g = 0 and
beta = 0 changes nothing: that is how a bucket's padding is masked.

Two entry points, each the Pallas kernel when the pool's gate admits it
(`nn/kv_pool._paged_gate`: backend and shape, the paged pair's flag) and
the `jax.numpy` form of the same arithmetic otherwise:

- `gdn_chunk_scan`, a prompt from S = 0, in chunks of 64 tokens. Inside a
  chunk the recurrence is solved in closed form (the WY / UT transform:
  U = (I + A)^-1 (beta V - beta Gamma K S_0), A the strictly lower part of
  beta_i Gamma_i / Gamma_j k_i . k_j), which is parallel over chunks and
  runs as batched XLA products (`_chunk_prepare`); what is sequential, the
  state from chunk to chunk, is the kernel: grid (head groups, chunks),
  the state carried in float32 in VMEM, three products a chunk.
- `gdn_step`, one token of every decode slot: grid (slots, head groups),
  the state block read, updated on the VPU and written back in place
  (`input_output_aliases`): a decode step moves each state once each way.

The serving state's layout is [slots, dk, heads * dv] float32: dk = 96
rows are whole sublane tiles and heads * dv = 5760 = 45 x 128 lanes, so
nothing is padded ([slots, heads, 96, 192] pads 192 lanes to 256, a third
more bytes a step). A head's columns are not lane-aligned in it (192 is
1.5 tiles), so the step kernel never slices a head: q and k are spread
over their heads' lanes by a 0/1 matrix on the MXU (exact: one product a
lane) and everything else is elementwise over [dk, lanes of a group].

q, k (L2-normalised, q scaled) and v arrive in float32 or in the
activations' dtype; the scan multiplies float32 at `highest` precision,
the update spreads q and k as bfloat16 terms (`_bf16_terms`) and does the
rest in float32 on the VPU. Inference only: no vjp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .flash_attention import _Z, _cparams, _interpret, _vmem

__all__ = ["CHUNK", "gdn_chunk_scan", "gdn_chunk_scan_ref", "gdn_step",
           "gdn_step_ref", "gdn_step_supported", "state_layout"]

CHUNK = 64
_SOLVE_BLOCK = 16      # (I + A)^-1: row by row at 16, merged to 32 and 64
# heads of one grid step, where they divide: the scan's step holds six
# blocks a head, double-buffered (10 heads asked for 17.6 MB of the 16 MiB
# of scoped VMEM), the update's one state block of 96 x 1920 float32
_SCAN_HEADS, _STEP_HEADS = 5, 10
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def state_layout(state):
    """[..., heads, dk, dv] -> the serving layout [..., dk, heads * dv]."""
    *lead, n, dk, dv = state.shape
    return jnp.moveaxis(state, -3, -2).reshape(*lead, dk, n * dv)


def _heads_per_step(heads, most, lanes_per_head=None):
    """The largest divisor of `heads` up to `most` (whose lanes are whole
    128-lane tiles, when `lanes_per_head` is given), else all."""
    for g in range(min(heads, most), 0, -1):
        if heads % g == 0 and (lanes_per_head is None
                               or (g * lanes_per_head) % 128 == 0):
            return g
    return heads


# --------------------------------------------------------------------------
# the chunked scan (prefill)
# --------------------------------------------------------------------------

def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def _unit_lower_inverse(a):
    """(I + A)^-1 for strictly lower-triangular A [..., C, C]: forward
    substitution row by row on the diagonal blocks of 16 (exact, where a
    Neumann product cancels powers of A that grow with correlated keys),
    then [[M1, 0], [A21, M2]]^-1 = [[M1^-1, 0], [-M2^-1 A21 M1^-1,
    M2^-1]] twice, to 32 and to the whole chunk."""
    size, b = a.shape[-1], _SOLVE_BLOCK
    n = size // b
    blocks = jnp.stack([a[..., i * b:(i + 1) * b, i * b:(i + 1) * b]
                        for i in range(n)], axis=-3)
    eye = jnp.eye(b, dtype=a.dtype)
    t = jnp.broadcast_to(eye, blocks.shape)
    for i in range(1, b):
        # rows below i are still the identity's and A[i, m >= i] = 0
        row = eye[i] - jnp.einsum("...m,...mj->...j", blocks[..., i, :], t,
                                  precision=_HIGHEST)
        t = t.at[..., i, :].set(row)
    while n > 1:
        t1, t2 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        a21 = jnp.stack(
            [a[..., (2 * p + 1) * b:(2 * p + 2) * b,
               2 * p * b:(2 * p + 1) * b] for p in range(n // 2)], axis=-3)
        low = -_mm(_mm(t2, a21), t1)
        t = jnp.concatenate(
            [jnp.concatenate([t1, jnp.zeros_like(t1)], axis=-1),
             jnp.concatenate([low, t2], axis=-1)], axis=-2)
        n, b = n // 2, b * 2
    return t[..., 0, :, :]


@jax.jit
def _chunk_prepare(q, k, v, g, beta):
    """What is parallel over chunks. q, k [B, NC, C, dk], v [B, NC, C, dv],
    g, beta [B, NC, C] float32 (B = batch x heads) -> float32
    (w [.., C, dk], u0 [.., C, dv], qg [.., C, dk], attn [.., C, C],
    kd_t [.., dk, C], decay [.., 1, 1]) with, from a chunk's entering
    state S:  U = u0 - w S;  O = qg S + attn U;  S' = decay S + kd_t U."""
    size = q.shape[-2]
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    gamma = jnp.cumsum(g, axis=-1)                       # log Gamma_i <= 0
    ratio = gamma[..., :, None] - gamma[..., None, :]    # log Gamma_i/Gamma_j
    tril = jnp.tril(jnp.ones((size, size), bool))
    decay_ij = jnp.exp(jnp.where(tril, ratio, -jnp.inf))  # j <= i, else 0
    kk = _mm(k, jnp.swapaxes(k, -1, -2))
    a = jnp.where(jnp.tril(tril, -1),
                  beta[..., :, None] * decay_ij * kk, 0.0)
    t = _unit_lower_inverse(a)
    big = jnp.exp(gamma)                                  # Gamma_i
    w = _mm(t, (beta * big)[..., None] * k)
    u0 = _mm(t, beta[..., None] * v)
    attn = decay_ij * _mm(q, jnp.swapaxes(k, -1, -2))
    qg = big[..., None] * q
    to_end = jnp.exp(gamma[..., -1:] - gamma)             # Gamma_C/Gamma_j
    kd_t = jnp.swapaxes(to_end[..., None] * k, -1, -2)
    return w, u0, qg, attn, kd_t, big[..., -1:, None]


def _chunk_scan_kernel(w_ref, u0_ref, qg_ref, attn_ref, kdt_ref, decay_ref,
                       o_ref, s_ref, s_scr):
    """Grid (head groups, chunks), chunks sequential. One step: G heads of
    one chunk, batched products (Mosaic unrolls the heads)."""
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[:] = jnp.zeros_like(s_scr)

    def bmm(a, b):
        return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                                   precision=_HIGHEST,
                                   preferred_element_type=F32)

    s = s_scr[:]                                    # [G, dk, dv]
    u = u0_ref[...] - bmm(w_ref[...], s)            # [G, C, dv]
    o_ref[...] = bmm(qg_ref[...], s) + bmm(attn_ref[...], u)
    s_scr[:] = decay_ref[...] * s + bmm(kdt_ref[...], u)

    @pl.when(ic == pl.num_programs(1) - 1)
    def _flush():
        s_ref[...] = s_scr[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_chunk_call(w, u0, qg, attn, kd_t, decay, *, interpret):
    heads, chunks, size, dk = w.shape
    dv = u0.shape[-1]
    g = _heads_per_step(heads, _SCAN_HEADS)

    def at(ig, ic):
        return (ig, ic, _Z, _Z)

    def block(*tail):
        return pl.BlockSpec((g, None) + tail, at)

    return pl.pallas_call(
        _chunk_scan_kernel,
        grid=(heads // g, chunks),
        in_specs=[block(size, dk), block(size, dv), block(size, dk),
                  block(size, size), block(dk, size), block(1, 1)],
        out_specs=[block(size, dv),
                   pl.BlockSpec((g, dk, dv), lambda ig, ic: (ig, _Z, _Z))],
        out_shape=[jax.ShapeDtypeStruct((heads, chunks, size, dv), F32),
                   jax.ShapeDtypeStruct((heads, dk, dv), F32)],
        scratch_shapes=[_vmem((g, dk, dv), F32)],
        compiler_params=_cparams("parallel", "arbitrary"),
        interpret=interpret,
    )(w, u0, qg, attn, kd_t, decay)


@jax.jit
def _chunk_scan_jnp(w, u0, qg, attn, kd_t, decay):
    """The kernel's arithmetic as a `lax.scan` over chunks."""
    def one_chunk(s, xs):
        w, u0, qg, attn, kd_t, decay = xs
        u = u0 - _mm(w, s)
        return decay * s + _mm(kd_t, u), _mm(qg, s) + _mm(attn, u)

    heads, _, _, dk = w.shape
    s0 = jnp.zeros((heads, dk, u0.shape[-1]), F32)
    s, o = jax.lax.scan(one_chunk, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (w, u0, qg, attn, kd_t, decay)))
    return jnp.moveaxis(o, 0, 1), s


def _to_chunks(x, pad):
    """[b, s, n, ...] -> [b * n, chunks, CHUNK, ...], zero-padded."""
    b, s, n = x.shape[:3]
    x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = jnp.moveaxis(x, 2, 1).reshape(b * n, (s + pad) // CHUNK, CHUNK,
                                      *x.shape[3:])
    return x


def _chunk_scan(q, k, v, g, beta, scan):
    b, s, n, _ = q.shape
    dv = v.shape[-1]
    pad = -s % CHUNK   # zeros with g = 0, beta = 0: tokens that do nothing
    parts = _chunk_prepare(*(_to_chunks(x, pad) for x in (
        q, k, v, g.astype(F32), beta.astype(F32))))
    o, state = scan(*parts)
    o = o.reshape(b, n, s + pad, dv)[:, :, :s]
    return jnp.moveaxis(o, 1, 2), state.reshape(b, n, *state.shape[1:])


def gdn_chunk_scan_ref(q, k, v, g, beta):
    """`gdn_chunk_scan` in `jax.numpy`: the gate's fallback, the CPU path
    and the kernel's oracle."""
    return _chunk_scan(q, k, v, g, beta, _chunk_scan_jnp)


def gdn_chunk_scan(q, k, v, g, beta):
    """The recurrence over a sequence from S = 0. q, k [b, s, n, dk] (k
    L2-normalised, q too and scaled), v [b, s, n, dv], g (log alpha <= 0)
    and beta [b, s, n] -> (o [b, s, n, dv] float32, the state after the
    last token [b, n, dk, dv] float32). Any s: padded to whole chunks
    with tokens that change nothing."""
    from ...nn.kv_pool import _paged_gate
    if not _paged_gate("gdn_chunk_scan", False,
                       lambda: q.shape[-1] % 8 == 0):
        return gdn_chunk_scan_ref(q, k, v, g, beta)
    from . import run_guarded
    b, s, n, _ = q.shape
    heads = _heads_per_step(b * n, _SCAN_HEADS)
    return run_guarded(
        "gdn_chunk_scan",
        lambda: _chunk_scan(q, k, v, g, beta, functools.partial(
            _gdn_chunk_call, interpret=_interpret())),
        heads_per_step=heads, grid_steps=b * n // heads * -(-s // CHUNK))


# --------------------------------------------------------------------------
# the state update (decode)
# --------------------------------------------------------------------------

def gdn_step_ref(state, q, k, v, alpha, beta):
    """`gdn_step` in `jax.numpy`."""
    b, dk, lanes = state.shape
    n = q.shape[1]
    s = state.reshape(b, dk, n, lanes // n) \
        * alpha.astype(F32)[:, None, :, None]
    k, q = k.astype(F32), q.astype(F32)
    ks = jnp.einsum("bnk,bknv->bnv", k, s, precision=_HIGHEST)
    u = beta.astype(F32)[..., None] * (v.astype(F32) - ks)
    s = s + jnp.einsum("bnk,bnv->bknv", k, u, precision=_HIGHEST)
    o = jnp.einsum("bnk,bknv->bnv", q, s, precision=_HIGHEST)
    return o, s.reshape(b, dk, lanes)


def _step_kernel(s_ref, k_ref, q_ref, vec_ref, spread_ref, s_out, o_out):
    """Grid (slots, head groups). s [dk, L] float32, L the group's lanes;
    k, q [parts, dk, Gp] bfloat16 (a head a column; `parts` terms that
    add up to the value, `_bf16_terms`); vec [3, L]: v, alpha, beta along
    the lanes; spread [Gp, L] 0/1: each product is one term times one."""
    def over_lanes(x_ref):
        return sum(jnp.dot(x_ref[p], spread_ref[...],
                           preferred_element_type=F32)
                   for p in range(x_ref.shape[0]))

    ke, qe = over_lanes(k_ref), over_lanes(q_ref)        # [dk, L]
    v, alpha, beta = vec_ref[0:1, :], vec_ref[1:2, :], vec_ref[2:3, :]
    s = s_ref[...] * alpha
    u = beta * (v - jnp.sum(ke * s, axis=0, keepdims=True))
    s = s + ke * u
    s_out[...] = s
    o_out[...] = jnp.sum(qe * s, axis=0, keepdims=True)


def _bf16_terms(x):
    """[terms, ...] bfloat16 that add up to x: x itself when it is
    bfloat16, else its two leading terms (16 bits of mantissa; the MXU
    spreads each exactly, a float32 product would take six passes)."""
    if x.dtype == jnp.bfloat16:
        return x[None]
    x = x.astype(F32)
    hi = x.astype(jnp.bfloat16)
    return jnp.stack([hi, (x - hi.astype(F32)).astype(jnp.bfloat16)])


def _columns(x, groups, width):
    """[b, n, dk] -> [b, groups, terms, dk, width] bfloat16: a group's
    heads as columns, zero columns up to `width`."""
    b, n, dk = x.shape
    x = jnp.swapaxes(x.reshape(b, groups, n // groups, dk), -1, -2)
    x = jnp.pad(x, ((0, 0),) * 3 + ((0, width - n // groups),))
    return jnp.moveaxis(_bf16_terms(x), 0, 2)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gdn_step_call(state, q, k, v, alpha, beta, *, interpret):
    b, dk, lanes = state.shape
    n, dv = v.shape[1], v.shape[2]
    g = _heads_per_step(n, _STEP_HEADS, dv)
    groups, width, span = n // g, -(-g // 16) * 16, g * dv
    spread = (np.arange(width)[:, None]
              == np.arange(span)[None, :] // dv).astype(jnp.bfloat16)
    k, q = _columns(k, groups, width), _columns(q, groups, width)
    vec = jnp.stack([v.astype(F32).reshape(b, lanes),
                     jnp.repeat(alpha.astype(F32), dv, axis=1),
                     jnp.repeat(beta.astype(F32), dv, axis=1)], axis=1)

    def lanes_at(i, ig):
        return (i, _Z, ig)

    def columns_at(i, ig):
        return (i, ig, _Z, _Z, _Z)

    def columns(x):
        return pl.BlockSpec((None, None, x.shape[2], dk, width), columns_at)

    state, o = pl.pallas_call(
        _step_kernel,
        grid=(b, groups),
        in_specs=[pl.BlockSpec((None, dk, span), lanes_at),
                  columns(k), columns(q),
                  pl.BlockSpec((None, 3, span), lanes_at),
                  pl.BlockSpec((width, span), lambda i, ig: (_Z, _Z))],
        out_specs=[pl.BlockSpec((None, dk, span), lanes_at),
                   pl.BlockSpec((None, 1, span), lanes_at)],
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((b, 1, lanes), F32)],
        input_output_aliases={0: 0},
        compiler_params=_cparams("parallel", "parallel"),
        interpret=interpret,
    )(state, k, q, vec, jnp.asarray(spread))
    return o.reshape(b, n, dv), state


def gdn_step_supported(state_shape, heads) -> bool:
    """Static predicate: [slots, dk, heads * dv] with dk whole sublane
    tiles; the lanes of a head group are then whole tiles or all."""
    return len(state_shape) == 3 and state_shape[1] % 8 == 0 \
        and state_shape[2] % int(heads) == 0


def gdn_step(state, q, k, v, alpha, beta):
    """One token of every slot. state [b, dk, n * dv] float32 (the serving
    layout, updated in place where the caller donates it); q, k [b, n, dk];
    v [b, n, dv]; alpha, beta [b, n] -> (o [b, n, dv] float32, state)."""
    from ...nn.kv_pool import _paged_gate
    n = q.shape[1]
    if not _paged_gate("gdn_step", False, lambda: gdn_step_supported(
            tuple(state.shape), n)):
        return gdn_step_ref(state, q, k, v, alpha, beta)
    from . import run_guarded
    g = _heads_per_step(n, _STEP_HEADS, v.shape[2])
    return run_guarded(
        "gdn_step",
        lambda: _gdn_step_call(state, q, k, v, alpha, beta,
                           interpret=_interpret()),
        heads_per_step=g, grid_steps=state.shape[0] * (n // g))
